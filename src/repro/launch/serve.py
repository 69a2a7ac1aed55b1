"""Serving launcher: ``python -m repro.launch.serve --arch <id> ...``.

Builds the DOLMA-aware batched engine (params + KV cache cataloged as data
objects; placement decided against the HBM budget) and runs a synthetic
request stream, reporting batched decode throughput. :func:`load_model` and
:func:`serve` are the same path ``chip_smoke.py`` drives on the chip.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, ModelConfig, get_config, reduced_config
from repro.core.telemetry import Telemetry
from repro.launch.cache import enable_compile_cache
from repro.models import get_model
from repro.serving import EngineConfig, ServingEngine


def load_model(arch: str, *, full: bool, seed: int,
               n_layers: int | None = None) -> tuple[ModelConfig, dict]:
    """Config and seeded random params: the published widths with ``full``,
    the reduced float32 config otherwise; ``n_layers`` cuts the depth."""
    cfg = get_config(arch)
    if not full:
        cfg = reduced_config(cfg, dtype=jnp.float32)
    if n_layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    init = jax.jit(get_model(cfg).init_params, static_argnums=1)
    return cfg, init(jax.random.PRNGKey(seed), cfg)


def serve(cfg: ModelConfig, params: dict, engine_cfg: EngineConfig, *,
          waves: int, prompt_len: int, new_tokens: int, seed: int,
          telemetry: Telemetry | None = None,
          ) -> tuple[ServingEngine, list[tuple[np.ndarray, np.ndarray]]]:
    """Build the engine and run ``waves`` independent full-batch waves of
    prompts drawn from ``seed``. Returns the engine and each wave's
    ``(prompts, generated tokens)``."""
    engine = ServingEngine(cfg, params, engine_cfg, telemetry=telemetry)
    rng = np.random.default_rng(seed)
    results = []
    for _ in range(waves):
        engine.reset()  # independent request waves
        prompts = rng.integers(
            0, cfg.vocab_size, (engine_cfg.max_batch, prompt_len)
        ).astype(np.int32)
        results.append((prompts, engine.generate(prompts, max_new=new_tokens)))
    return engine, results


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b", choices=ARCH_IDS)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--requests", type=int, default=2, help="request waves")
    ap.add_argument("--hbm-budget-gb", type=float, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg, params = load_model(args.arch, full=args.full, seed=args.seed)
    budget = int(args.hbm_budget_gb * 1e9) if args.hbm_budget_gb else None
    t0 = time.perf_counter()
    engine, results = serve(
        cfg, params,
        EngineConfig(max_batch=args.batch, max_len=args.max_len,
                     hbm_budget_bytes=budget),
        waves=args.requests, prompt_len=args.prompt_len,
        new_tokens=args.new_tokens, seed=args.seed,
    )
    dt = time.perf_counter() - t0
    print(f"arch={cfg.name} placement={engine.stats()['placement']}")
    for wave, (_prompts, out) in enumerate(results):
        print(f"wave {wave}: {out.shape[0]} requests x {out.shape[1]} tokens")
    total_toks = sum(out.size for _prompts, out in results)
    print(f"{total_toks} tokens in {dt:.2f}s = {total_toks/dt:.1f} tok/s batched")


if __name__ == "__main__":
    main()
