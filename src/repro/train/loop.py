"""Training loop: data prefetch, async checkpointing, straggler watchdog.

Fault-tolerance model (designed for 1000+ nodes, exercised at CPU scale):
  * async checkpoints every ``ckpt_every`` steps (delta-encoded, atomic);
  * startup restores the latest checkpoint — including onto a different
    mesh shape (elastic restart after node loss);
  * a step-time watchdog flags stragglers (> ``straggler_factor`` x rolling
    median); the mitigation hook records the event and (in a real cluster)
    triggers re-slicing — here it feeds the fault-injection tests;
  * the data stream is a deterministic function of (seed, step): replaying
    after restore is exact.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import statistics
import time
from typing import Any, Callable

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import ModelConfig
from repro.data.pipeline import (
    PrefetchingLoader,
    SyntheticTokenDataset,
    device_put_fn,
)
from repro.models.sharding import (
    batch_pspec_tree,
    current_mesh,
    get_rules,
    opt_pspec_tree,
    params_pspec_tree,
)
from repro.optim import AdamWConfig
from repro.train.step import TrainStepConfig, init_train_state, make_train_step


@dataclasses.dataclass
class LoopConfig:
    steps: int = 100
    batch: int = 8
    seq: int = 128
    seed: int = 0
    log_every: int = 10
    ckpt_every: int = 50
    ckpt_dir: str | None = None
    straggler_factor: float = 3.0
    straggler_window: int = 20


@dataclasses.dataclass
class LoopResult:
    final_step: int
    losses: list
    step_times: list
    straggler_events: list
    restored_from: int | None
    params: Any  # final params, placed as the loop placed them


def _named(mesh: Mesh, specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def _state_shardings(model_cfg: ModelConfig, step_cfg: TrainStepConfig,
                    opt_cfg: AdamWConfig, mesh: Mesh):
    """``(params, opt_state)`` sharding trees on ``mesh`` under the current
    logical-axis rules (shapes only: nothing is allocated)."""
    params_abs, opt_abs = jax.eval_shape(
        functools.partial(init_train_state, model_cfg=model_cfg,
                          step_cfg=step_cfg, opt_cfg=opt_cfg),
        jax.random.PRNGKey(0),
    )
    p_specs = params_pspec_tree(
        params_abs, expert_sharding=model_cfg.expert_sharding, mesh=mesh
    )
    return (_named(mesh, p_specs),
            _named(mesh, opt_pspec_tree(opt_abs, p_specs, mesh)))


def train(
    model_cfg: ModelConfig,
    step_cfg: TrainStepConfig,
    opt_cfg: AdamWConfig,
    loop_cfg: LoopConfig,
    *,
    on_step: Callable[[int, dict], None] | None = None,
    fault_hook: Callable[[int], None] | None = None,
) -> LoopResult:
    """Run the loop. Returns loss/timing history and the final params.

    Under ``use_mesh(mesh)`` params and optimizer state are created in the
    layout the sharding rules give them, batches land in theirs, and the
    step keeps that layout; without a mesh everything sits on the default
    device.
    """
    key = jax.random.PRNGKey(loop_cfg.seed)
    init = functools.partial(init_train_state, model_cfg=model_cfg,
                             step_cfg=step_cfg, opt_cfg=opt_cfg)
    step_fn = make_train_step(model_cfg, step_cfg, opt_cfg)
    dataset = SyntheticTokenDataset(model_cfg, loop_cfg.batch, loop_cfg.seq,
                                    seed=loop_cfg.seed)
    mesh = current_mesh()
    shardings = put_fn = None
    if mesh is None:
        params, opt_state = init(key)
        train_step = jax.jit(step_fn, donate_argnums=(0, 1))
    else:
        shardings = _state_shardings(model_cfg, step_cfg, opt_cfg, mesh)
        params, opt_state = jax.jit(init, out_shardings=shardings)(key)
        train_step = jax.jit(
            step_fn, donate_argnums=(0, 1),
            out_shardings=(*shardings, NamedSharding(mesh, P())),
        )
        # specs resolved here: the loader thread sees neither the mesh nor
        # the rule overrides, which are thread-local
        b_specs = batch_pspec_tree(dataset.batch_at(0), mesh)
        put_fn = device_put_fn(mesh, lambda _batch: b_specs)

    ckpt = CheckpointManager(loop_cfg.ckpt_dir) if loop_cfg.ckpt_dir else None
    start_step = 0
    restored_from = None
    if ckpt is not None:
        restored = ckpt.restore(params, opt_state, shardings=shardings)
        if restored is not None:
            params, opt_state = restored["params"], restored["opt_state"]
            start_step = restored["step"]
            restored_from = start_step

    loader = PrefetchingLoader(dataset, start_step=start_step, put_fn=put_fn)

    losses: list[float] = []
    times: list[float] = []
    stragglers: list[dict] = []
    window: collections.deque = collections.deque(maxlen=loop_cfg.straggler_window)

    try:
        step = start_step
        while step < loop_cfg.steps:
            data_step, batch = next(loader)
            t0 = time.perf_counter()
            params, opt_state, metrics = train_step(params, opt_state, batch)
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            step = data_step + 1
            losses.append(loss)
            times.append(dt)

            # straggler watchdog
            if len(window) >= 5:
                med = statistics.median(window)
                if dt > loop_cfg.straggler_factor * med:
                    stragglers.append({"step": step, "dt": dt, "median": med})
            window.append(dt)

            if on_step is not None:
                on_step(step, metrics)
            if fault_hook is not None:
                fault_hook(step)  # tests raise here to simulate node failure
            if ckpt is not None and step % loop_cfg.ckpt_every == 0:
                ckpt.save(step, params, opt_state, metadata={
                    "rules": {k: list(v) if isinstance(v, tuple) else v
                              for k, v in get_rules().items()},
                    "arch": model_cfg.name,
                    "seed": loop_cfg.seed,
                })
            if step % loop_cfg.log_every == 0:
                print(f"step {step}: loss={loss:.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} {dt*1e3:.0f}ms",
                      flush=True)
    finally:
        loader.close()
        if ckpt is not None:
            ckpt.wait()

    return LoopResult(
        final_step=step,
        losses=losses,
        step_times=times,
        straggler_events=stragglers,
        restored_from=restored_from,
        params=params,
    )
