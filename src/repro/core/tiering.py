"""Compiled-graph tiering: DOLMA placement applied to a JAX step function.

Two backends realize a :class:`PlacementPlan` inside the compiled graph:

* ``host_offload`` — REMOTE leaves get ``memory_kind="pinned_host"`` on their
  sharding: HBM is the local tier, host DRAM the remote tier. Fetch = a
  device copy XLA schedules; the dual buffer is the explicit next-layer
  prefetch carried through :func:`tiered_scan`.
* ``fsdp_stream`` — REMOTE leaves are sharded along the data axis and
  all-gathered per layer inside the scan (peer HBM is the remote tier). This
  is pure SPMD and compiles on every backend; it is the default for the
  multi-pod dry-run.

Either way, :func:`tiered_scan` provides the paper's dual-buffer shape: the
scan carry holds the *current* layer's materialized weights while the *next*
layer's fetch is issued before the current layer's compute — so the fetch has
no data dependence on the compute and the scheduler can overlap them. The
"access barrier deferred to first use" (§5) is the data dependence of layer
k+1's first matmul on its own gather, rather than a global barrier.

:func:`tiered_scan` is the single engine for the layer loop; it composes the
dual buffer with sqrt-L activation checkpointing instead of treating them as
mutually exclusive:

* **remat off** — a flat scan whose carry holds the next layer's fetched
  weights (the classic dual buffer).
* **remat on** — depth ``L`` splits into ``n_outer`` checkpointed blocks of
  ``n_inner`` layers (:func:`_block_split`). The dual-buffer carry lives in
  the *inner* scan, entirely inside each block's remat boundary: the gathered
  weights are recomputed during the block's backward pass, never saved across
  the forward — prefetch no longer defeats FSDP/offload. Only ``n_outer``
  activation carries persist, and a ``remote_carry_fn`` hook can place those
  on the remote tier (``pinned_host`` where the SPMD partitioner takes it,
  the fsdp-sharded spec otherwise) so saved activations obey the same
  placement budget as weights. The cost of the boundary is one unoverlapped
  fetch per block (the first layer's weights cannot be prefetched from the
  previous block without being saved).

The anti-hoisting barrier between the carry stack and the layer body is
:func:`grad_safe_barrier` — a ``jax.custom_vjp`` identity that applies
``jax.lax.optimization_barrier`` in both the forward and backward pass. The
raw primitive has no differentiation rule (``jax.grad`` through it raises
``NotImplementedError``), so the custom VJP both fixes autodiff by
construction and keeps the barrier's scheduling effect on the cotangents.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Literal

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.metadata import Tier
from repro.core.objects import ObjectCatalog, ObjectKind
from repro.core.placement import PlacementPlan, PlacementPolicy

TieringMode = Literal["none", "host_offload", "fsdp_stream"]

#: Modeled HBM stream rate (GB/s) that prices a synthesized profile's compute.
_MODELED_HBM_GBPS = 819.0


@dataclasses.dataclass(frozen=True)
class TieringConfig:
    """How params/opt-state tier out of HBM during the layer scan.

    ``local_fraction`` is the share of (param + opt state) bytes kept
    resident (or ``"auto"`` to let the sizing solver pick it);
    ``degradation_target`` is the slowdown fraction the solver sizes for
    (0.16 = paper knee). ``prefetch`` enables the dual-buffer weight
    fetch one layer ahead of compute.
    """

    mode: TieringMode = "fsdp_stream"
    # Fraction of (param + opt state) bytes allowed to stay in HBM; "auto"
    # defers to the cost-model sizing solver (plan_for_params needs a
    # WorkloadProfile then — see repro.core.sizing).
    local_fraction: float | str = 1.0
    # Degradation target the "auto" solver sizes for (paper knee: 16%).
    degradation_target: float = 0.16
    prefetch: bool = True  # dual-buffer prefetch in the layer scan
    # Keep the dual buffer on when the layer scan is rematerialized: the
    # prefetch carry moves inside the block-level remat boundary (recomputed,
    # not saved). Off = the pre-unification behaviour (prefetch only without
    # remat, overlap left to XLA's latency-hiding scheduler).
    prefetch_under_remat: bool = True
    # Which axis FSDP-shards the remote leaves over.
    fsdp_axis: str = "data"


@functools.cache
def supports_host_offload() -> bool:
    """Whether demoted objects can live in ``pinned_host`` memory here.

    Decided by the platform: on a TPU host DRAM is the remote tier, so the
    probe placement must succeed and any failure raises; other backends
    return False and demotions stay in the plan and the memory pool.
    """
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return False
    sharding = jax.sharding.SingleDeviceSharding(dev, memory_kind="pinned_host")
    jax.block_until_ready(jax.device_put(jnp.zeros((8,), jnp.float32), sharding))
    return True


@functools.cache
def _offload_spmd_probe(mesh: jax.sharding.Mesh) -> bool:
    axis = mesh.axis_names[-1]
    dev_sh = NamedSharding(mesh, P(None, axis))
    host_sh = NamedSharding(mesh, P(None, axis), memory_kind="pinned_host")

    def step(p, m):
        m2 = 0.9 * m + 0.1 * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - m2).astype(p.dtype), m2

    pa = jax.ShapeDtypeStruct((16, mesh.shape[axis] * 8), jnp.bfloat16)
    ma = jax.ShapeDtypeStruct(pa.shape, jnp.float32)
    jax.jit(step, in_shardings=(dev_sh, host_sh),
            out_shardings=(dev_sh, host_sh)).lower(pa, ma).compile()
    return True


def supports_host_offload_spmd(mesh: jax.sharding.Mesh) -> bool:
    """Whether pinned_host in/out shardings compile under SPMD on this mesh.

    Decided by the mesh's platform: on TPU the probe compile must succeed
    (a failure raises); XLA-CPU (the dry-run container) rejects memory-space
    annotations in the SPMD partitioner, so it returns False and the
    optimizer falls down the bf16/int8 moment ladder instead (DESIGN.md §2).
    """
    if mesh.devices.flat[0].platform != "tpu":
        return False
    return _offload_spmd_probe(mesh)


def plan_for_params(
    params: Any,
    *,
    config: TieringConfig,
    opt_state: Any = None,
    access_counts: dict[str, int] | None = None,
    profile: Any = None,
    telemetry: Any = None,
) -> PlacementPlan:
    """Build a placement plan over the persistent objects of a train step.

    Parameters are read every step (forward + backward ⇒ 2 reads, 1 write);
    optimizer moments are read+written once. Those defaults reproduce the
    policy inputs DOLMA's allocator interposition observes; callers may
    override with measured ``access_counts`` from an ObjectCatalog trace.

    With ``config.local_fraction == "auto"`` the HBM budget is chosen by the
    quantitative sizing solver: pass a recorded ``WorkloadProfile``, or omit
    ``profile`` to have one synthesized from this catalog (each leaf fetched
    once per step — :func:`repro.core.sizing.synthetic_profile`) with the
    step's compute time estimated from leaf bytes at HBM bandwidth.
    """
    catalog = ObjectCatalog()
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        name = "params" + jax.tree_util.keystr(path)
        from repro.core.objects import DataObject

        n_reads = (access_counts or {}).get(name, 2)
        catalog.add(
            DataObject(
                name=name,
                shape=tuple(leaf.shape),
                dtype=leaf.dtype,
                kind=ObjectKind.PARAM,
                n_reads=n_reads,
                n_writes=1,
            )
        )
    if opt_state is not None:
        for path, leaf in jax.tree_util.tree_leaves_with_path(opt_state):
            name = "opt" + jax.tree_util.keystr(path)
            from repro.core.objects import DataObject

            catalog.add(
                DataObject(
                    name=name,
                    shape=tuple(leaf.shape),
                    dtype=leaf.dtype,
                    kind=ObjectKind.OPT_STATE,
                    n_reads=1,
                    n_writes=1,
                )
            )
    if config.local_fraction == "auto" and profile is None:
        from repro.core.sizing import synthetic_profile

        # one read of every leaf per step at a modeled HBM stream rate
        # approximates the step's compute floor — enough for the solver to
        # price demotions; a model input, never reported as a device rate
        compute_us = catalog.total_bytes / (_MODELED_HBM_GBPS * 1e3)
        profile = synthetic_profile(catalog, compute_us_per_step=compute_us,
                                    source="plan_for_params")
    plan = PlacementPolicy().plan(
        catalog,
        local_fraction=config.local_fraction,
        profile=profile,
        degradation_target=config.degradation_target,
    )
    if telemetry is not None and telemetry.enabled:
        telemetry.instant("tiering.plan", track="tiering", t_us=0.0,
                          **plan.summary())
        telemetry.gauge("tiering.local_bytes", plan.local_bytes)
        telemetry.gauge("tiering.remote_bytes", plan.remote_bytes)
    return plan


def leaf_sharding(
    mesh: jax.sharding.Mesh,
    spec: P,
    *,
    tier: Tier,
    config: TieringConfig,
    leading_dim: int | None = None,
) -> NamedSharding:
    """Sharding for one leaf given its DOLMA tier."""
    if tier is Tier.REMOTE:
        if config.mode == "host_offload" and supports_host_offload():
            return NamedSharding(mesh, spec, memory_kind="pinned_host")
        if config.mode == "fsdp_stream":
            # shard the leading (stacked-layer) dim over the fsdp axis when
            # divisible; otherwise fall back to the base spec.
            if leading_dim is not None and config.fsdp_axis in mesh.shape:
                ax = mesh.shape[config.fsdp_axis]
                if leading_dim % ax == 0 and (not spec or spec[0] is None):
                    new_spec = P(config.fsdp_axis, *tuple(spec)[1:]) if spec else P(
                        config.fsdp_axis
                    )
                    return NamedSharding(mesh, new_spec)
    return NamedSharding(mesh, spec)


@jax.custom_vjp
def grad_safe_barrier(x):
    """Identity whose forward AND backward apply an XLA optimization barrier.

    ``jax.lax.optimization_barrier`` has no differentiation rule, so placing
    it raw between the carry stack and the layer body makes every grad-taking
    caller crash with ``NotImplementedError``. This custom-VJP identity keeps
    the barrier's anti-hoisting effect (stops XLA moving a convert of the
    whole saved-carry stack out of the backward loop, which would materialize
    a full-precision copy of every saved carry) while being transparent to
    autodiff: the cotangent passes through its own barrier, so the backward
    loop gets the same scheduling fence.
    """
    return jax.lax.optimization_barrier(x)


def _gsb_fwd(x):
    return jax.lax.optimization_barrier(x), None


def _gsb_bwd(_res, g):
    return (jax.lax.optimization_barrier(g),)


grad_safe_barrier.defvjp(_gsb_fwd, _gsb_bwd)


def _block_split(n_layers: int) -> tuple[int, int]:
    """Factor ``n_layers = n_outer * n_inner`` minimizing ``n_outer + n_inner``.

    Roles are explicit: ``n_outer`` is the number of checkpointed blocks (the
    count of carries *saved* across the forward), ``n_inner`` the layers per
    block (the transient recompute depth during one block's backward). Only
    exact factorizations are produced — ``n_outer * n_inner == n_layers``
    always — so prime depths degenerate to ``(1, n_layers)``: a single block,
    full recompute. ``n_outer <= n_inner`` by construction (the smaller
    divisor is taken as the saved-carry count).
    """
    if n_layers < 1:
        raise ValueError(f"_block_split: n_layers must be >= 1, got {n_layers}")
    best = (1, n_layers)
    for n_outer in range(1, int(n_layers ** 0.5) + 1):
        if n_layers % n_outer == 0:
            n_inner = n_layers // n_outer
            if n_outer + n_inner < best[0] + best[1]:
                best = (n_outer, n_inner)
    assert best[0] * best[1] == n_layers, (
        f"_block_split produced ragged blocking {best} for depth {n_layers}"
    )
    return best


def _check_stack_depth(stacked_params: Any, n_layers: int) -> None:
    leads = {
        t.shape[0] for t in jax.tree.leaves(stacked_params) if jnp.ndim(t) >= 1
    }
    if leads and leads != {n_layers}:
        raise ValueError(
            f"tiered_scan: stacked_params leading dims {sorted(leads)} do not "
            f"all equal n_layers={n_layers}; the scan would silently "
            "mis-block. Slice or restack the params to the depth you scan."
        )


def _default_fetch(stacked, i):
    return jax.tree.map(
        lambda x: jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False),
        stacked,
    )


def tiered_scan(
    layer_fn: Callable[[Any, Any], Any],
    carry: Any,
    stacked_params: Any,
    *,
    n_layers: int,
    remat: bool = False,
    policy=None,
    prefetch: bool = True,
    fetch_fn: Callable[[Any, jax.Array], Any] | None = None,
    min_layers: int = 12,
    unroll: int = 1,
    remote_carry_fn: Callable[[Any], Any] | None = None,
):
    """Scan ``layer_fn`` over ``n_layers`` stacked layers: the unified engine.

    ``stacked_params``: pytree whose leaves have leading dim ``n_layers``
    (possibly host-offloaded / FSDP-sharded). ``fetch_fn(stacked, i)``
    materializes layer *i*'s weights in the local tier (default: dynamic
    index, which XLA turns into a copy/all-gather per the leaves' shardings).

    ``prefetch=True`` gives the paper's dual buffer: the carry holds the next
    layer's materialized weights, fetched one step ahead of use so the
    fetch has no data dependence on the current layer's compute. Prefetch
    changes only *when* fetches are issued, never their indices or the math
    on the carry — loss and grads are bit-identical to ``prefetch=False``.

    ``remat=True`` composes that with two-level (sqrt-L) checkpointing:
    ``n_outer`` blocks of ``n_inner`` layers (:func:`_block_split`), each
    block ``jax.checkpoint``-ed, each layer ``jax.checkpoint``-ed inside it.
    The dual-buffer carry lives in the inner scan, inside the block's remat
    boundary: gathered weights are recomputed during the block's backward,
    not saved across the forward. Saved state is ``n_outer`` activation
    carries (+ ``n_inner`` transiently during one block's backward; with
    prefetch also ``n_inner`` transient weight fetches). Depths below
    ``min_layers`` use flat per-layer remat (``n_outer=n_layers``,
    ``n_inner=1``) — fewer recomputes at O(L) saved carries; prefetch has
    nothing to overlap inside a 1-layer block and degenerates gracefully.

    ``remote_carry_fn`` (remat only) is applied to each saved outer-block
    carry — the hook that places persistent activation memory on the remote
    tier (see :func:`remote_carry_placer`).
    """
    _check_stack_depth(stacked_params, n_layers)
    if fetch_fn is None:
        fetch_fn = _default_fetch

    if not remat:
        if not prefetch:
            def body(c, i):
                """Demand-fetch scan step: fetch layer ``i``, then compute."""
                return layer_fn(c, fetch_fn(stacked_params, i)), None

            carry, _ = jax.lax.scan(
                body, carry, jnp.arange(n_layers), unroll=unroll
            )
            return carry

        p0 = fetch_fn(stacked_params, jnp.asarray(0, jnp.int32))

        def body(state, i):
            """Dual-buffer scan step: post fetch ``i+1``, compute layer ``i``."""
            c, cur = state
            # issue the next fetch *before* compute: no data dependence
            # between them, so the scheduler overlaps DMA/all-gather with
            # the matmuls.
            nxt = fetch_fn(
                stacked_params,
                jnp.minimum(i + 1, n_layers - 1).astype(jnp.int32),
            )
            c = layer_fn(c, cur)
            return (c, nxt), None

        (carry, _), _ = jax.lax.scan(
            body, (carry, p0), jnp.arange(n_layers), unroll=unroll
        )
        return carry

    # --- remat path ---------------------------------------------------------
    n_outer, n_inner = (
        (n_layers, 1) if n_layers < min_layers else _block_split(n_layers)
    )

    # per-layer checkpoint; the fetch sits inside the boundary so the weight
    # gather is re-issued (not stored) when this layer's backward recomputes
    def layer_at(c, i):
        """One rematerialized layer, fetching its own weights by index."""
        return layer_fn(grad_safe_barrier(c), fetch_fn(stacked_params, i))

    layer_at = jax.checkpoint(layer_at, policy=policy)

    # prefetch variant: current weights arrive via the (inner) carry
    def layer_with(c, p):
        """One rematerialized layer, weights arriving via the scan carry."""
        return layer_fn(grad_safe_barrier(c), p)

    layer_with = jax.checkpoint(layer_with, policy=policy)

    def block_fn(c, start):
        """Layers [start, start + n_inner) — runs inside one remat boundary."""
        if not prefetch or n_inner == 1:
            def body(cc, j):
                """Demand-fetch step inside the remat block."""
                return layer_at(cc, start + j), None

            c, _ = jax.lax.scan(
                body, c, jnp.arange(n_inner, dtype=jnp.int32), unroll=unroll
            )
            return c

        # dual buffer inside the boundary: p0 and every carried fetch are
        # recomputed during this block's backward, never saved forward
        p0 = fetch_fn(stacked_params, start)

        def body(state, j):
            """Dual-buffer step inside the remat block (fetches recomputed)."""
            cc, cur = state
            nxt = fetch_fn(
                stacked_params,
                jnp.minimum(start + j + 1, n_layers - 1).astype(jnp.int32),
            )
            cc = layer_with(cc, cur)
            return (cc, nxt), None

        (c, _), _ = jax.lax.scan(
            body, (c, p0), jnp.arange(n_inner, dtype=jnp.int32), unroll=unroll
        )
        return c

    if n_inner > 1:  # flat mode keeps the single (per-layer) checkpoint level
        block_fn = jax.checkpoint(block_fn, policy=policy)

    def outer_body(c, g):
        """Run remat block ``g`` and re-place its saved carry off-HBM."""
        c = block_fn(c, (g * n_inner).astype(jnp.int32))
        if remote_carry_fn is not None:
            c = remote_carry_fn(c)
        return c, None

    if remote_carry_fn is not None:
        carry = remote_carry_fn(carry)  # the initial carry is saved too
    carry, _ = jax.lax.scan(
        outer_body, carry, jnp.arange(n_outer, dtype=jnp.int32)
    )
    return carry


def remote_carry_placer(
    mesh: jax.sharding.Mesh | None,
    config: TieringConfig | None = None,
    *,
    spec_fn: Callable[[Any], P] | None = None,
) -> Callable[[Any], Any] | None:
    """Build a ``remote_carry_fn`` placing saved block carries off-HBM.

    Where the SPMD partitioner accepts memory-space annotations
    (:func:`supports_host_offload_spmd` — TPU backends), each saved carry
    leaf is constrained to its own spec with ``memory_kind="pinned_host"``:
    a pure memory-space move, no resharding, host DRAM as the remote tier.
    Otherwise the leaf is constrained to the (fsdp-/batch-sharded) spec
    itself — peer HBM as the remote tier, the ``fsdp_stream`` realization.
    Returns ``None`` when there is no mesh (single-host tests).

    ``spec_fn(leaf) -> PartitionSpec`` supplies the logical spec of a carry
    leaf (callers resolve their own activation axis names); default is
    fully replicated, which is only sensible for host offload.
    """
    if mesh is None:
        return None
    config = config or TieringConfig()
    host = config.mode != "none" and supports_host_offload_spmd(mesh)

    def spec_of(leaf) -> P:
        """Logical partition spec of a carry leaf (default: replicated)."""
        if spec_fn is not None:
            return spec_fn(leaf)
        return P(*([None] * jnp.ndim(leaf)))

    def place_leaf(leaf):
        """Constrain one carry leaf to the remote tier (host or peer HBM)."""
        if jnp.ndim(leaf) < 2:  # scalars / small aux stay local
            return leaf
        spec = spec_of(leaf)
        if host:
            sharding = NamedSharding(mesh, spec, memory_kind="pinned_host")
        else:
            sharding = NamedSharding(mesh, spec)
        return jax.lax.with_sharding_constraint(leaf, sharding)

    return lambda c: jax.tree.map(place_leaf, c)


# ---------------------------------------------------------------------------
# deprecated shims — PR 2 unified both scans into tiered_scan
# ---------------------------------------------------------------------------

def prefetch_scan(
    layer_fn: Callable[[Any, Any], Any],
    carry: Any,
    stacked_params: Any,
    *,
    n_layers: int,
    prefetch: bool = True,
    fetch_fn: Callable[[Any, jax.Array], Any] | None = None,
    unroll: int = 1,
):
    """Deprecated: use :func:`tiered_scan` (``remat=False``)."""
    return tiered_scan(
        layer_fn, carry, stacked_params, n_layers=n_layers, remat=False,
        prefetch=prefetch, fetch_fn=fetch_fn, unroll=unroll,
    )


def blocked_remat_scan(layer_fn, carry, stacked_params, *, n_layers: int,
                       policy=None, min_layers: int = 12):
    """Deprecated: use :func:`tiered_scan` (``remat=True``)."""
    return tiered_scan(
        layer_fn, carry, stacked_params, n_layers=n_layers, remat=True,
        policy=policy, prefetch=False, min_layers=min_layers,
    )
