"""Logical-axis sharding rules.

Model code annotates tensors with *logical* axis names; this module resolves
them to physical mesh axes using a mutable rule table. The rule table is the
primary perf-iteration lever (EXPERIMENTS.md §Perf): hillclimbing a cell means
swapping rules here (or per-call overrides), re-lowering, and re-reading the
roofline terms — no model code changes.

Resolution drops any physical axis that does not divide the dimension (e.g.
kv_heads=1 on a 16-way 'model' axis), which keeps every (arch x shape x mesh)
cell compilable by construction.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Logical axis -> physical mesh axis (or tuple of axes). None = replicated.
DEFAULT_RULES: dict[str, tuple[str, ...] | str | None] = {
    "batch": ("pod", "data"),     # global batch
    "seq": None,                  # sequence inside attention blocks
    "seq_sp": "model",            # sequence-parallel activation storage
    "heads": "model",
    "kv_heads": "model",
    "ff": "model",
    "vocab": "model",
    "expert": "model",
    "expert_ff": None,
    "d_model": None,
    "layers": None,               # stacked-layer dim; "data" => FSDP streaming
    # decode KV-cache length: takes whatever batch left free ('model' when
    # KV heads don't divide it; both axes at batch=1 long-context)
    "kv_len": ("model", "data"),
    "state": None,                # SSM state dim
    "fsdp": None,                 # weight non-model dim; "data" => FSDP (ZeRO-3)
}


class _Rules(threading.local):
    def __init__(self):
        self.rules = dict(DEFAULT_RULES)
        self.mesh: Mesh | None = None


_ctx = _Rules()


def get_rules() -> dict:
    return dict(_ctx.rules)


@contextlib.contextmanager
def use_rules(**overrides):
    old = dict(_ctx.rules)
    _ctx.rules.update(overrides)
    try:
        yield
    finally:
        _ctx.rules = old


@contextlib.contextmanager
def use_mesh(mesh: Mesh | None):
    old = _ctx.mesh
    _ctx.mesh = mesh
    try:
        yield
    finally:
        _ctx.mesh = old


def current_mesh() -> Mesh | None:
    return _ctx.mesh


def _physical(axes: tuple[str, ...] | str | None, mesh: Mesh) -> tuple[str, ...]:
    if axes is None:
        return ()
    if isinstance(axes, str):
        axes = (axes,)
    return tuple(a for a in axes if a in mesh.shape)


def resolve_spec(
    shape: Sequence[int], names: Sequence[str | None], mesh: Mesh | None = None
) -> P:
    """Logical names -> PartitionSpec, dropping non-dividing axes."""
    mesh = mesh or _ctx.mesh
    if mesh is None:
        return P(*([None] * len(names)))
    if len(shape) != len(names):
        raise ValueError(f"shape rank {len(shape)} != names {names}")
    entries = []
    used: set[str] = set()  # a mesh axis may appear at most once per spec
    for dim, name in zip(shape, names):
        if name is None:
            entries.append(None)
            continue
        phys = _physical(_ctx.rules.get(name), mesh)
        group = 1
        kept = []
        for a in phys:
            if a not in used and dim % (group * mesh.shape[a]) == 0:
                kept.append(a)
                group *= mesh.shape[a]
        used.update(kept)
        if not kept:
            entries.append(None)
        elif len(kept) == 1:
            entries.append(kept[0])
        else:
            entries.append(tuple(kept))
    return P(*entries)


def constrain(x: jax.Array, *names: str | None) -> jax.Array:
    """with_sharding_constraint under the current mesh+rules (no-op if none)."""
    mesh = _ctx.mesh
    if mesh is None:
        return x
    spec = resolve_spec(x.shape, names, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(shape: Sequence[int], names: Sequence[str | None],
                   mesh: Mesh | None = None) -> NamedSharding | None:
    mesh = mesh or _ctx.mesh
    if mesh is None:
        return None
    return NamedSharding(mesh, resolve_spec(shape, names, mesh))


# ---------------------------------------------------------------------------
# parameter / batch / cache logical-name assignment
# ---------------------------------------------------------------------------

# last-path-key -> logical names of the *core* (unstacked) rank
_PARAM_CORE_NAMES: dict[str, tuple] = {
    "wq": (None, "heads"),
    "wk": (None, "kv_heads"),
    "wv": (None, "kv_heads"),
    "wo": ("heads", None),
    "w_down": ("ff", None),
    "embedding": ("vocab", None),
    "router": (None, None),
    "wq_a": (None, None),
    "wq_b": (None, "heads"),
    "wkv_a": (None, None),
    "wkv_b": (None, "heads"),
    "in_proj": (None, None),
    "out_proj": (None, None),
    "conv_w": (None, None),
    "proj": (None, None),
}


def param_logical_names(path, leaf_ndim: int, *, expert_sharding: str = "expert",
                        fsdp: bool = False):
    """Logical names for one parameter leaf, given its pytree path.

    With ``fsdp=True`` every replicated core dim of a matrix weight is named
    'fsdp' (rule-mapped to the data axis): the weight is ZeRO-3 sharded and
    all-gathered per layer inside the prefetch scan — the distributed form of
    DOLMA's remote-object streaming.
    """
    import jax.tree_util as jtu

    keys = [k.key for k in path if isinstance(k, jtu.DictKey)]
    last = keys[-1] if keys else ""
    in_moe = "moe" in keys and last in ("w_gate", "w_up", "w_down")

    if in_moe:
        if last == "w_down":
            core = ("expert", None, None) if expert_sharding == "expert" \
                else (None, "ff", None)
        else:
            core = ("expert", None, None) if expert_sharding == "expert" \
                else (None, None, "ff")
    elif last in ("w_gate", "w_up"):
        core = (None, "ff")
    elif last in _PARAM_CORE_NAMES:
        core = _PARAM_CORE_NAMES[last]
    else:
        core = tuple([None] * min(leaf_ndim, 2))

    extra = leaf_ndim - len(core)
    if extra < 0:  # scalar / vector leaf (norm scales etc.)
        return tuple([None] * leaf_ndim)
    if fsdp and len(core) >= 2:
        # every replicated core dim becomes an fsdp candidate; resolve_spec's
        # divisibility + one-axis-per-spec tracking picks the dims that work
        # (e.g. mixtral's (E=8, d, ff) expert weights shard d, not E)
        core = tuple("fsdp" if c is None else c for c in core)
    lead = (["layers"] + [None] * (extra - 1)) if extra >= 1 else []
    return tuple(lead) + core


# decode-cache leaf name -> logical names (rank-matched at resolution)
_CACHE_CORE_NAMES: dict[str, tuple] = {
    "k": ("layers", "batch", "kv_len", "kv_heads", None),
    "v": ("layers", "batch", "kv_len", "kv_heads", None),
    "shared_k": ("layers", "batch", "kv_len", "kv_heads", None),
    "shared_v": ("layers", "batch", "kv_len", "kv_heads", None),
    "ck": ("layers", "batch", None, "kv_heads", None),
    "cv": ("layers", "batch", None, "kv_heads", None),
    "c": ("layers", "batch", "kv_len", None),
    "kr": ("layers", "batch", "kv_len", None),
    "conv": ("layers", "batch", None, None),
    "state": ("layers", "batch", "heads", None, None),
    "pos": (),
}


def cache_pspec_tree(abstract_cache, mesh: Mesh | None = None):
    """PartitionSpec pytree for a decode cache."""
    import jax.tree_util as jtu

    def spec_of(path, leaf):
        keys = [k.key for k in path if isinstance(k, jtu.DictKey)]
        last = keys[-1] if keys else ""
        names = _CACHE_CORE_NAMES.get(last, tuple([None] * len(leaf.shape)))
        if len(names) != len(leaf.shape):
            names = tuple([None] * len(leaf.shape))
        return resolve_spec(leaf.shape, names, mesh)

    return jtu.tree_map_with_path(spec_of, abstract_cache)


def batch_pspec_tree(abstract_batch, mesh: Mesh | None = None):
    """PartitionSpec pytree for a train/prefill batch."""
    import jax.tree_util as jtu

    def spec_of(_path, leaf):
        names = ("batch",) + tuple([None] * (len(leaf.shape) - 1))
        return resolve_spec(leaf.shape, names, mesh)

    return jtu.tree_map_with_path(spec_of, abstract_batch)


def opt_pspec_tree(opt_abs, params_pspecs, mesh: Mesh | None = None):
    """Specs for an optimizer state pytree (moments mirror their params).

    Handles QTensor moment leaves: ``codes`` shares the param's spec (same
    shape); ``scale`` (last dim = blocks) keeps the leading entries and
    replicates its last dim.
    """
    import jax.tree_util as jtu

    def is_spec(x):
        return isinstance(x, P)
    by_path = {
        jtu.keystr(path): spec
        for path, spec in jtu.tree_leaves_with_path(params_pspecs, is_leaf=is_spec)
    }

    def spec_of(path, leaf):
        keys = list(path)
        first = keys[0].key if isinstance(keys[0], jtu.DictKey) else None
        if first not in ("m", "v"):
            return P()
        sub = keys[1:]
        attr = None
        if sub and isinstance(sub[-1], jtu.GetAttrKey):
            attr = sub[-1].name
            sub = sub[:-1]
        base = by_path.get(jtu.keystr(tuple(sub)))
        if base is None:
            return P(*([None] * len(leaf.shape)))
        if attr == "scale":
            entries = tuple(base)[: len(leaf.shape) - 1]
            entries = entries + tuple(
                [None] * (len(leaf.shape) - len(entries))
            )
            return P(*entries)
        return base

    return jtu.tree_map_with_path(spec_of, opt_abs)


def shard_factor(spec: P, mesh: Mesh) -> int:
    f = 1
    for entry in spec:
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        for a in axes:
            f *= mesh.shape[a]
    return f


def params_pspec_tree(abstract_params, *, expert_sharding: str = "expert",
                      fsdp: bool = False, mesh: Mesh | None = None):
    """PartitionSpec pytree for a params pytree (abstract or concrete)."""
    import jax.tree_util as jtu

    def spec_of(path, leaf):
        names = param_logical_names(
            path, len(leaf.shape), expert_sharding=expert_sharding, fsdp=fsdp
        )
        return resolve_spec(leaf.shape, names, mesh)

    return jtu.tree_map_with_path(spec_of, abstract_params)
