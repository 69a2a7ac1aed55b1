"""Mixture-of-Experts FFN with capacity-based grouped dispatch.

Dispatch is *per group* (a group = one batch row in train/prefill, one data
shard's tokens in decode) so the expert sort never crosses the batch-sharded
axis — no global sort collectives. Experts shard over the 'model' mesh axis
(``expert_sharding="expert"``, deepseek: 256/16 = 16 per device) or replicate
with tensor-parallel expert hidden (``"tensor"``, mixtral: 8 experts < 16-way
axis).

Routing (:func:`route`) is softmax top-k (mixtral) or DeepSeek-V3's
group-limited sigmoid gate. With ``n_experts_held`` the layer is one
expert-parallel shard without a mesh: it routes over every expert and
computes its own experts' part (:func:`_moe_ffn_held`, dropless).

From the DOLMA perspective expert weights are the canonical remote object:
large, cold (top-k of E per token), write-once-per-step — the placement
policy demotes them first (asserted in tests).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels import kernel_backend
from repro.kernels.moe_experts import held_experts
from repro.models.layers import Params, _init, mlp, mlp_init
from repro.models.sharding import constrain


def moe_init(key, cfg: ModelConfig) -> Params:
    d, E, ffe = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    E_loc = cfg.n_experts_local
    ks = jax.random.split(key, 5)
    p = {
        "router": _init(ks[0], (d, E), jnp.float32),
        "w_gate": _init(ks[1], (E_loc, d, ffe), cfg.dtype),
        "w_up": _init(ks[2], (E_loc, d, ffe), cfg.dtype),
        "w_down": _init(ks[3], (E_loc, ffe, d), cfg.dtype,
                        scale=1.0 / np.sqrt(ffe)),
    }
    if cfg.scoring_func == "sigmoid":
        p["router_bias"] = jnp.zeros((E,), jnp.float32)
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(ks[4], cfg, d_ff=cfg.n_shared_experts * ffe)
    return p


def route(p: Params, x: jax.Array, cfg: ModelConfig):
    """The router's choice for tokens ``x`` (..., d): ``(top_i, top_w,
    probs)``, the ``top_k`` expert ids and combine weights of each token and
    the (..., E) distribution the load-balance loss reads.

    ``softmax`` (mixtral): the top-k of the softmax, renormalised. ``sigmoid``
    (DeepSeek-V3 ``noaux_tc``): scores ``s = sigmoid(x W_r)``; experts are
    *chosen* by ``s + router_bias`` -- only among the ``topk_group`` of
    ``n_group`` groups whose two best biased scores sum highest -- and
    *weighted* by the unbiased ``s``, normalised over the chosen and scaled
    by ``routed_scaling_factor``.
    """
    logits = x.astype(jnp.float32) @ p["router"]
    if cfg.scoring_func == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_i = jax.lax.top_k(probs, cfg.top_k)
        return top_i, top_w / jnp.sum(top_w, axis=-1, keepdims=True), probs
    s = jax.nn.sigmoid(logits)
    choice = s + p["router_bias"]
    if cfg.n_group > 1:
        grouped = choice.reshape(*choice.shape[:-1], cfg.n_group, -1)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, keep = jax.lax.top_k(group_score, cfg.topk_group)
        kept = jnp.any(keep[..., :, None] == jnp.arange(cfg.n_group), axis=-2)
        choice = jnp.where(kept[..., None], grouped, -jnp.inf).reshape(s.shape)
    _, top_i = jax.lax.top_k(choice, cfg.top_k)
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    top_w = (top_s / jnp.sum(top_s, axis=-1, keepdims=True)
             * cfg.routed_scaling_factor)
    return top_i, top_w, s / jnp.sum(s, axis=-1, keepdims=True)


def _balance_loss(probs: jax.Array, top_i: jax.Array, cfg: ModelConfig):
    """Switch/Mixtral load-balance loss; counts via scatter-add (a one-hot
    would materialize a (tokens, k, E) f32 tensor per layer)."""
    E = cfg.n_experts
    me = jnp.mean(probs.reshape(-1, E), axis=0)  # (E,)
    n_tok = probs.size // E
    ce = jnp.zeros((E,), jnp.float32).at[top_i.reshape(-1)].add(1.0 / n_tok)
    return E * jnp.sum(me * ce) / cfg.top_k


def _local_slots(top_i, top_w, lo: int, E_loc: int):
    """Each routing slot's local expert id (``E_loc`` = not held here) and
    its combine weight (0 where not held), for experts ``[lo, lo + E_loc)``."""
    local = (top_i >= lo) & (top_i < lo + E_loc)
    li = jnp.where(local, top_i - lo, E_loc).astype(jnp.int32)
    return li, jnp.where(local, top_w, 0.0)


def held_counts(top_i: jax.Array, cfg: ModelConfig) -> jax.Array:
    """``[routed, active]`` int32 of the stacked routing ``top_i``
    ``(n_layers, ..., k)``: token slots routed to the experts held here,
    and (layer, held expert) pairs with at least one token."""
    E_loc = cfg.n_experts_local
    held = cfg.expert_shard * E_loc + jnp.arange(E_loc)
    flat = top_i.reshape(top_i.shape[0], -1)
    hits = flat[:, :, None] == held                       # (nL, slots, E_loc)
    return jnp.stack([jnp.sum(hits), jnp.sum(jnp.any(hits, axis=1))]
                     ).astype(jnp.int32)


def _expert_weight_names(cfg: ModelConfig):
    if cfg.expert_sharding == "expert":
        return ("expert", None, None), ("expert", None, None)
    return ("expert", None, "ff"), ("expert", "ff", None)  # tensor-parallel


EXPERT_KEYS = ("w_gate", "w_up", "w_down")


def expert_tensors(p: Params, layer: jax.Array | None = None
                   ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Single read point for the routed-expert weights (every path). With
    ``layer``, ``p`` holds every layer's experts stacked (leading dim) and
    the layer's are returned.

    ``p`` is either the plain param dict ``moe_init`` builds or the
    assembled view an :class:`repro.serving.expert_paging.ExpertParamStore`
    produces. In the paged case non-resident experts' rows are zeros: a
    capacity slot that received no valid token carries an exact-zero input,
    and 0-rows keep it exactly zero through silu/einsum — so the output is
    bit-identical to untiered whenever every *routed* expert is resident
    (the serving engine's fixpoint step loop enforces exactly that).
    """
    w = tuple(p[k] for k in EXPERT_KEYS)
    return w if layer is None else tuple(t[layer] for t in w)


def moe_ffn(
    p: Params,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    groups: int | None = None,
    return_routing: bool = False,
    layer: jax.Array | None = None,
):
    """Returns (output, load_balance_aux_loss[, (top_i, top_p)]). x: (B, S, d).

    ``layer``: ``p``'s routed-expert tensors are stacked over the MoE layers
    and this layer's are used. A layer loop hands the stacks whole so that
    the held-expert kernel reads the layer's experts from them in place (a
    kernel call would have XLA copy a scanned slice out first).

    When a mesh with a >1 'model' axis is active and experts divide it, the
    expert-parallel shard_map path is used: dispatch/combine run locally per
    expert shard (tokens are replicated across 'model') with a single combine
    psum per layer — instead of letting SPMD materialize cross-shard gathers
    and scatter-adds (EXPERIMENTS.md §Perf, deepseek cell).

    With ``return_routing`` the per-token router decision is appended to the
    result: ``top_i``/``top_p`` of shape (B, S, k) — the signal the serving
    engine's expert pager feeds its per-expert router-mass EMA.
    """
    from repro.models.sharding import current_mesh

    if cfg.n_experts_held:
        return _moe_ffn_held(p, x, cfg, groups=groups,
                             return_routing=return_routing, layer=layer)
    if layer is not None:
        p = {**p, **dict(zip(EXPERT_KEYS, expert_tensors(p, layer)))}
    mesh = current_mesh()
    if (
        mesh is not None
        and cfg.expert_sharding == "expert"
        and "model" in mesh.shape
        and mesh.shape["model"] > 1
        and cfg.n_experts % mesh.shape["model"] == 0
    ):
        return _moe_ffn_ep(p, x, cfg, mesh, groups=groups,
                           return_routing=return_routing)
    return _moe_ffn_dense(p, x, cfg, groups=groups,
                          return_routing=return_routing)


def _moe_ffn_dense(
    p: Params,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    groups: int | None = None,
    return_routing: bool = False,
):
    B, S, d = x.shape
    E, k, cf = cfg.n_experts, cfg.top_k, cfg.capacity_factor
    G = groups if groups is not None else B
    assert (B * S) % G == 0, f"tokens {B*S} not divisible by groups {G}"
    T = (B * S) // G  # tokens per dispatch group
    xt = x.reshape(G, T, d)

    top_i, top_p, probs = route(p, xt, cfg)  # (G,T,k), (G,T,k), (G,T,E)
    aux = _balance_loss(probs, top_i, cfg)

    cap = max(int(np.ceil(T * k / E * cf)), 1)

    # --- per-group sorted dispatch (no cross-group comms) ---
    flat_e = top_i.reshape(G, T * k)
    flat_w = top_p.reshape(G, T * k)
    flat_tok = jnp.broadcast_to(jnp.arange(T)[:, None], (T, k)).reshape(T * k)
    flat_tok = jnp.broadcast_to(flat_tok, (G, T * k))

    order = jnp.argsort(flat_e, axis=-1)
    se = jnp.take_along_axis(flat_e, order, axis=-1)
    stok = jnp.take_along_axis(flat_tok, order, axis=-1)
    sw = jnp.take_along_axis(flat_w, order, axis=-1)

    # position of each slot within its expert's contiguous run
    starts = jax.vmap(lambda row: jnp.searchsorted(row, jnp.arange(E)))(se)  # (G,E)
    pos = jnp.arange(T * k)[None, :] - jnp.take_along_axis(starts, se, axis=-1)
    # the pos >= 0 guard mirrors the EP path (_dispatch_local): searchsorted
    # keeps pos non-negative for well-formed expert ids, but the two paths
    # must share one validity definition so they can never drift (ISSUE 10)
    valid = (pos >= 0) & (pos < cap)
    dest = se * cap + jnp.where(valid, pos, 0)  # (G, T*k) in [0, E*cap)

    # gather tokens into (G, E, cap, d)
    src = jnp.take_along_axis(xt, stok[..., None], axis=1)  # (G,T*k,d)
    src = jnp.where(valid[..., None], src, 0)
    xg = jnp.zeros((G, E * cap, d), x.dtype)
    xg = jax.vmap(lambda buf, idx, val: buf.at[idx].add(val))(xg, dest, src)
    xg = xg.reshape(G, E, cap, d)
    xg = constrain(xg, "batch", "expert", None, None)

    # expert computation
    wn1, wn2 = _expert_weight_names(cfg)
    wg_t, wu_t, wd_t = expert_tensors(p)
    wg = constrain(wg_t, *wn1)
    wu = constrain(wu_t, *wn1)
    wd = constrain(wd_t, *wn2)
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xg, wg))
    h = h * jnp.einsum("gecd,edf->gecf", xg, wu)
    h = constrain(h, "batch", "expert", None, "expert_ff")
    yg = jnp.einsum("gecf,efd->gecd", h, wd)  # (G,E,cap,d)
    yg = constrain(yg, "batch", "expert", None, None)

    # combine back to tokens
    yflat = yg.reshape(G, E * cap, d)
    gathered = jnp.take_along_axis(yflat, dest[..., None], axis=1)  # (G,T*k,d)
    gathered = jnp.where(valid[..., None], gathered, 0) * sw[..., None].astype(x.dtype)
    out = jnp.zeros((G, T, d), x.dtype)
    out = jax.vmap(lambda buf, idx, val: buf.at[idx].add(val))(out, stok, gathered)
    out = out.reshape(B, S, d)

    if cfg.n_shared_experts:
        out = out + mlp(p["shared"], x)
    if return_routing:
        routing = (top_i.reshape(B, S, k), top_p.reshape(B, S, k))
        return out, aux, routing
    return out, aux


# ---------------------------------------------------------------------------
# expert-parallel dispatch (shard_map over the 'model' axis)
# ---------------------------------------------------------------------------

def _shard_map(body, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with a fallback to the pre-0.6 experimental API."""
    sm = getattr(jax, "shard_map", None)
    if sm is not None:
        return sm(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    from jax.experimental.shard_map import shard_map as sm_exp

    return sm_exp(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs)


def _maybe_pvary(x, axis: str | None):
    """``jax.lax.pvary`` where the varying-manual-axes machinery exists
    (jax >= 0.6); identity elsewhere (older shard_map tracks replication
    itself, and pvary's transpose-placement optimization does not apply)."""
    pvary = getattr(jax.lax, "pvary", None)
    if axis is None or pvary is None:
        return x
    typeof = getattr(jax, "typeof", None)
    if typeof is not None and axis in typeof(x).vma:
        return x
    return pvary(x, axis)


def _einsum_ffn(w_gate, w_up, w_down):
    """The experts' SwiGLU as jnp einsums over every expert: ``(xg, counts)
    -> yg`` for ``xg`` (G, E, cap, d); the counts are not needed."""
    def ffn(xg, counts):
        del counts
        h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xg, w_gate))
        h = h * jnp.einsum("gecd,edf->gecf", xg, w_up)
        return jnp.einsum("gecf,efd->gecd", h, w_down)
    return ffn


def _dispatch_local(xt, li, lw, E_loc, cap, expert_ffn, dtype,
                    axis: str | None = None):
    """Capacity dispatch among E_loc local experts (per-shard; no collectives
    besides the explicit pvary). xt: (G,T,d); li: (G,T*k) local expert ids
    with E_loc = non-local sentinel; lw: (G,T*k) combine weights (0 for
    non-local); ``expert_ffn(xg, counts)`` maps the (G,E_loc,cap,d) grouped
    tokens, expert e's ``counts[g, e]`` first, to the experts' outputs.
    Returns (G,T,d) partial sums.

    Grouping and combining are gathers: the slots sorted by expert put each
    expert's tokens in one run, so row c of expert e is sorted slot
    ``start[e] + c``, and each token's k slots are found again through the
    inverse of the sort (a scatter-add of the rows is many times slower on
    a TPU).

    ``axis``: inside shard_map, xt (replicated over the expert axis) is
    explicitly ``pvary``'d here. This does two things: (1) it works around
    shard_map autodiff dropping cross-shard cotangents through gathers whose
    operand is unvarying but whose indices vary, and (2) pvary's transpose IS
    the dx psum — placed at token granularity by construction, instead of
    XLA hoisting an all-reduce to the k-times-larger slot-level cotangent.
    """
    xt = _maybe_pvary(xt, axis)
    G, T, d = xt.shape
    k_slots = li.shape[1]
    flat_tok = jnp.broadcast_to(
        jnp.arange(T)[:, None], (T, k_slots // T)
    ).reshape(k_slots)
    flat_tok = jnp.broadcast_to(flat_tok, (G, k_slots))

    order = jnp.argsort(li, axis=-1)
    se = jnp.take_along_axis(li, order, axis=-1)
    stok = jnp.take_along_axis(flat_tok, order, axis=-1)
    sw = jnp.take_along_axis(lw, order, axis=-1)

    ids = jnp.arange(E_loc)
    starts = jax.vmap(lambda row: jnp.searchsorted(row, ids))(se)   # (G,E)
    ends = jax.vmap(lambda row: jnp.searchsorted(row, ids, side="right"))(se)
    counts = jnp.minimum(ends - starts, cap)
    pos = jnp.arange(k_slots)[None, :] - jnp.take_along_axis(
        starts, jnp.minimum(se, E_loc - 1), axis=-1
    )
    valid = (se < E_loc) & (pos < cap) & (pos >= 0)
    dest = jnp.where(valid, jnp.minimum(se, E_loc - 1) * cap + pos, 0)

    rows = starts[:, :, None] + jnp.arange(cap)                    # (G,E,cap)
    filled = jnp.arange(cap) < counts[:, :, None]
    tok = jnp.take_along_axis(
        stok, jnp.minimum(rows, k_slots - 1).reshape(G, -1), axis=1)
    xg = jnp.take_along_axis(xt, tok[..., None], axis=1).reshape(
        G, E_loc, cap, d)
    xg = jnp.where(filled[..., None], xg, 0).astype(dtype)

    yg = expert_ffn(xg, counts).reshape(G, E_loc * cap, d)

    gathered = jnp.take_along_axis(yg, dest[..., None], axis=1)
    gathered = jnp.where(valid[..., None], gathered, 0) * sw[..., None].astype(dtype)
    # back to token order: slot t*k + j of token t, then sum each token's k
    unsorted = jnp.take_along_axis(
        gathered, jnp.argsort(order, axis=-1)[..., None], axis=1)
    return jnp.sum(unsorted.reshape(G, T, k_slots // T, d), axis=2,
                   dtype=dtype)


def _moe_ffn_ep(
    p: Params,
    x: jax.Array,
    cfg: ModelConfig,
    mesh,
    *,
    groups: int | None = None,
    return_routing: bool = False,
):
    from repro.models.sharding import resolve_spec
    from jax.sharding import PartitionSpec as P

    B, S, d = x.shape
    E, k, cf = cfg.n_experts, cfg.top_k, cfg.capacity_factor
    n_shards = mesh.shape["model"]
    E_loc = E // n_shards

    # thread ``groups`` exactly like the dense path: G dispatch groups of
    # T = (B*S)/G tokens each set the per-expert capacity. The EP dispatch
    # runs per data shard, so every group must fall entirely within one
    # shard — contiguous batch sharding gives that iff n_data divides G.
    G = groups if groups is not None else B
    if G <= 0 or (B * S) % G:
        raise ValueError(
            f"moe groups={G} does not evenly partition {B}x{S} tokens"
        )
    n_data = mesh.shape.get("data", 1)
    if G % n_data:
        raise ValueError(
            f"moe groups={G} must be divisible by the data-shard count "
            f"{n_data} so each dispatch group stays within one shard"
        )
    T = (B * S) // G
    G_loc = G // n_data

    # routing is computed replicated (tiny dot); aux loss comes from it
    top_i, top_p, probs = route(p, x, cfg)
    aux = _balance_loss(probs, top_i, cfg)

    cap = max(int(np.ceil(T * k / E * cf)), 1)

    x_spec = resolve_spec(x.shape, ("batch", None, None), mesh)
    r_spec = resolve_spec(top_i.shape, ("batch", None, None), mesh)
    w1_spec = P("model", None, None)
    w2_spec = P("model", None, None)

    def body(x_l, topi_l, topp_l, wg_l, wu_l, wd_l):
        shard = jax.lax.axis_index("model")
        li, lw = _local_slots(topi_l, topp_l, shard * E_loc, E_loc)
        Bl, Sl, dl = x_l.shape
        part = _dispatch_local(x_l.reshape(G_loc, T, dl),
                               li.reshape(G_loc, T * k),
                               lw.reshape(G_loc, T * k),
                               E_loc, cap, _einsum_ffn(wg_l, wu_l, wd_l),
                               x_l.dtype, axis="model")
        return jax.lax.psum(part.reshape(Bl, Sl, dl), "model")

    wg, wu, wd = expert_tensors(p)
    out = _shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, r_spec, r_spec, w1_spec, w1_spec, w2_spec),
        out_specs=x_spec,
    )(x, top_i, top_p, wg, wu, wd)

    if cfg.n_shared_experts:
        out = out + mlp(p["shared"], x)
    if return_routing:
        return out, aux, (top_i, top_p)
    return out, aux


# ---------------------------------------------------------------------------
# the chip's share of the experts (expert parallelism, no mesh)
# ---------------------------------------------------------------------------

def _moe_ffn_held(
    p: Params,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    groups: int | None = None,
    return_routing: bool = False,
    layer: jax.Array | None = None,
):
    """The expert layer of one expert-parallel shard: routes over all
    ``n_experts``, computes the part of the result that its own
    ``n_experts_held`` experts (shard ``expert_shard``) give for the tokens
    routed to them, and adds the shared expert once. What the other shards'
    experts would add is left out; on one chip the layer runs without its
    exchange.

    Dropless: an expert appears at most once in a token's top-k, so a
    group's T tokens fill at most T slots of it, and the capacity is T.
    Compiled on a TPU with one dispatch group (decode), the experts' FFN is
    the :mod:`~repro.kernels.moe_experts` kernel.
    """
    from repro.models.sharding import current_mesh

    B, S, d = x.shape
    E_loc, k = cfg.n_experts_local, cfg.top_k
    G = groups if groups is not None else B
    assert (B * S) % G == 0, f"tokens {B*S} not divisible by groups {G}"
    T = (B * S) // G
    xt = x.reshape(G, T, d)
    top_i, top_w, probs = route(p, xt, cfg)
    aux = _balance_loss(probs, top_i, cfg)
    li, lw = _local_slots(top_i, top_w, cfg.expert_shard * E_loc, E_loc)
    if G == 1 and kernel_backend() == "pallas" and current_mesh() is None:
        stacks = expert_tensors(p)
        if layer is None:
            stacks, layer = tuple(t[None] for t in stacks), 0

        def ffn(xg, counts):
            return held_experts(xg[0], *stacks, counts[0], layer)[None]
    else:
        ffn = _einsum_ffn(*expert_tensors(p, layer))
    out = _dispatch_local(xt, li.reshape(G, T * k), lw.reshape(G, T * k),
                          E_loc, T, ffn, x.dtype).reshape(B, S, d)
    if cfg.n_shared_experts:
        out = out + mlp(p["shared"], x)
    if return_routing:
        return out, aux, (top_i.reshape(B, S, k), top_w.reshape(B, S, k))
    return out, aux
