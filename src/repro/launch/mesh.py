"""Production mesh construction + the latency-hiding XLA flag recipe.

A FUNCTION (not module-level state) so importing this module never touches
jax device initialization — the dry-run sets XLA_FLAGS before any jax import,
and :func:`apply_latency_hiding_flags` must be called the same way (before
the first jax import) by any launcher that wants the overlap recipe.
"""
from __future__ import annotations

import os

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None) -> jax.sharding.Mesh:
    """The one mesh constructor: every axis is ``AxisType.Auto``.

    ``jax.make_mesh`` gives ``Explicit`` axes by default since JAX 0.7, and
    ``with_sharding_constraint`` (what the logical-axis rules lower to)
    accepts only ``Auto`` axes. ``devices`` defaults to ``jax.devices()``.
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod (v5e); multi-pod adds a 2-pod DCN axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


# The measured-overlap recipe (PR 8): what the StreamingExecutor does by hand
# at data-object granularity — posting the next transfer before the current
# compute — the XLA scheduler can do inside a compiled graph for collectives
# and host<->device copies, IF asked. These flags are the asking. They are
# GPU-spelled (TPU enables the latency-hiding scheduler by default; on CPU
# they are unknown and must not be passed), so the recipe is gated on target.
LATENCY_HIDING_XLA_FLAGS = (
    "--xla_gpu_enable_async_collectives=true",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)


def apply_latency_hiding_flags(*, target: str = "gpu",
                               env: dict | None = None) -> str:
    """Append the overlap recipe to ``XLA_FLAGS`` (idempotent).

    Must run *before the first jax import* in the process — XLA reads the
    env var at backend initialization and never again (same contract as the
    dry-run's ``xla_force_host_platform_device_count``). Returns the final
    flag string. ``target`` other than ``"gpu"`` is a no-op: TPU already
    schedules async collectives eagerly, and CPU rejects the flags.
    """
    env = os.environ if env is None else env
    current = env.get("XLA_FLAGS", "")
    if target != "gpu":
        return current
    have = set(current.split())
    add = [f for f in LATENCY_HIDING_XLA_FLAGS if f not in have]
    if add:
        current = " ".join(filter(None, [current, *add]))
        env["XLA_FLAGS"] = current
    return current

