"""Bring-up check: the serving and streaming paths on one TPU.

    python chip_smoke.py [--seed N]        # one chip: device, serve, stream
    python chip_smoke.py --four-chips      # four chips: sharded training only

Phases, all in this one process (a TPU belongs to one process at a time):

* **device** -- the default JAX device must be a TPU; there is no CPU
  fallback.
* **serve** -- granite-8b at its published widths in bf16, depth cut to 8
  layers, served through ``repro.launch.serve``. A tight-HBM-budget engine
  whose KV leaves are demoted must emit the same tokens as an untiered one,
  the last decode step's logits must agree with ``model.forward`` over the
  same tokens, and every logit must be finite.
* **stream** -- ``StreamingExecutor`` matmul and attention chains with
  compiled Pallas kernels and a real host-to-HBM transfer for the streamed
  half: bit-identical to the untiered oracle, close to the float32 jnp
  reference.
* **four chips** (``--four-chips`` only) -- train steps of a 2-layer
  granite-8b on a (data=2, model=2) mesh through ``repro.train.loop.train``,
  against the same steps on one of the chips.

Earlier lines report compile seconds and blocked step latencies of this
bring-up run; they are not benchmark metrics. The last line of standard
output is one JSON object naming the device. Any failed check raises and the
script exits non-zero.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.exec import (  # noqa: E402
    StreamingExecutor,
    attention_chain,
    matmul_chain,
    untiered_oracle,
)
from repro.core.telemetry import Telemetry  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.serve import load_model, serve  # noqa: E402
from repro.models import get_model  # noqa: E402
from repro.models.sharding import use_mesh  # noqa: E402
from repro.optim import AdamWConfig  # noqa: E402
from repro.serving import EngineConfig  # noqa: E402
from repro.train.loop import LoopConfig, train  # noqa: E402
from repro.train.step import TrainStepConfig  # noqa: E402

#: granite-8b has 36 layers: 16.2 GB of bf16 weights, more than one v5e's
#: 16 GB of HBM. Holding the rest is the host tier's job, not this check's.
SERVE_LAYERS = 8
#: Decode logits vs ``model.forward`` (both bf16 weights and activations,
#: different op order): max |diff| over the largest |logit|. One bf16
#: rounding is 2**-8 relative; 8 layers of residual adds compound it.
LOGITS_RTOL = 5e-2
#: Streamed bf16 chain vs the float32 jnp reference: relative L2 error. Each
#: stage rounds its output to bf16 (2**-9 relative on average), 8 stages.
STREAM_RTOL = 2e-2
#: Sharded vs one-chip training loss, relative. The mesh splits matmul
#: contractions, whose bf16 partial sums are added in another order.
LOSS_RTOL = 1e-2


def check(ok: bool, what: str) -> None:
    """Raise (never ``assert``: ``-O`` strips it) when a check fails."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


class CompileMeter:
    """Backend compile seconds and persistent-cache hits/writes, per phase,
    read from JAX's monitoring events (a cache hit records its load time)."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.hits = 0
        self.writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1

    def report(self, phase: str) -> None:
        print(f"{phase}: compile {self.seconds:.3f} s, persistent cache "
              f"hits {self.hits} writes {self.writes}", flush=True)
        self.seconds, self.hits, self.writes = 0.0, 0, 0


def device_phase(n_chips: int) -> jax.Device:
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU: jax.devices()[0].platform is "
            f"{dev.platform!r}; this check runs only on a TPU"
        )
    check(len(devices) == n_chips,
          f"{n_chips} chip(s) expected, JAX sees {len(devices)}")
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    return dev


def serve_phase(cfg, params, *, max_batch: int, max_len: int,
                prompt_len: int, new_tokens: int, waves: int,
                seed: int) -> None:
    tel = Telemetry()
    engine_cfg = EngineConfig(max_batch=max_batch, max_len=max_len)
    run = dict(waves=waves, prompt_len=prompt_len, new_tokens=new_tokens,
               seed=seed)
    engine, results = serve(cfg, params, engine_cfg, telemetry=tel, **run)
    print(f"serve: {waves} waves x {max_batch} requests, {prompt_len}-token "
          f"prompts, {new_tokens} new tokens; blocked decode step of the "
          f"last wave p50 {tel.gauges['serving.p50_step_us']:.1f} us, "
          f"p99 {tel.gauges['serving.p99_step_us']:.1f} us", flush=True)

    # the last decode step against one full-sequence forward
    prompts, out = results[-1]
    tokens = jnp.asarray(np.concatenate([prompts, out], axis=1))
    forward = jax.jit(get_model(cfg).forward, static_argnums=2)
    want = forward(params, {"tokens": tokens}, cfg)[0][:, -1, :cfg.vocab_size]
    got = engine.last_logits[:, 0, :cfg.vocab_size]
    want, got = np.asarray(want, np.float32), np.asarray(got, np.float32)
    check(np.isfinite(got).all() and np.isfinite(want).all(),
          "decode and forward logits are finite")
    err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    print(f"serve: decode vs forward logits max|diff|/max|logit| {err:.3e} "
          f"(bound {LOGITS_RTOL})", flush=True)
    check(err <= LOGITS_RTOL, "decode logits agree with model.forward")
    catalog_bytes = engine.catalog.total_bytes
    del engine

    tight_cfg = dataclasses.replace(engine_cfg,
                                    hbm_budget_bytes=catalog_bytes // 20,
                                    pool_nodes=1)
    tight, tight_results = serve(cfg, params, tight_cfg, **run)
    demoted_kv = [n for n in tight.placement.remote_names()
                  if n.startswith("cache")]
    check(bool(demoted_kv), "the tight budget demotes KV leaves")
    for (_p, a), (_q, b) in zip(results, tight_results):
        check(np.array_equal(a, b), "tiered tokens equal untiered tokens")
    check(np.isfinite(np.asarray(tight.last_logits, np.float32)).all(),
          "tiered logits are finite")
    summary = tight.placement_summary()
    check(summary["offload_memory_kind"] == "pinned_host",
          "demoted objects map to pinned_host")
    print(f"serve: tight budget {tight_cfg.hbm_budget_bytes} B demotes "
          f"{len(demoted_kv)} KV leaves to {summary['offload_memory_kind']}; "
          f"tokens identical to untiered: checks passed", flush=True)


def _chain_reference(kind: str, stages, x0) -> np.ndarray:
    """The chain in float32 through ``kernels/ref.py``."""
    x = jnp.asarray(x0, jnp.float32)
    with jax.default_matmul_precision("float32"):
        for st in stages:
            p = {k: jnp.asarray(a, jnp.float32) for k, a in st.params.items()}
            if kind == "matmul":
                x = ref.matmul_ref(x, p["w"])
            else:
                o = ref.flash_ref(x.transpose(0, 2, 1, 3),
                                  p["k"].transpose(0, 2, 1, 3),
                                  p["v"].transpose(0, 2, 1, 3),
                                  causal=st.kwargs["causal"],
                                  window=st.kwargs["window"])
                x = o.transpose(0, 2, 1, 3)
    return np.asarray(x)


def stream_phase(chains: dict) -> None:
    for kind, (stages, x0) in chains.items():
        oracle = untiered_oracle(stages, x0)
        ex = StreamingExecutor(stages, prefetch=True, throttle=0.0)
        try:
            check(ex.interpret is False, "kernels run compiled, not interpreted")
            plan = ex.plan_tiers(0.5)
            ex.warmup(x0)
            res = ex.run(x0)
            got = np.asarray(res.output)
            check(got.dtype == oracle.dtype and np.array_equal(
                got.view(np.uint8), oracle.view(np.uint8)),
                f"{kind} chain bit-identical to the untiered oracle")
            want = _chain_reference(kind, stages, x0)
            got32 = got.astype(np.float32)
            check(np.isfinite(got32).all(), f"{kind} chain output is finite")
            err = float(np.linalg.norm(got32 - want) / np.linalg.norm(want))
            reads = [m for m in ex.engine.measurements if m[0] == "read"]
            print(f"stream {kind}: {len(stages)} stages, "
                  f"{len(plan.remote_names())} streamed; run {res.elapsed_us:.1f}"
                  f" us blocked (compute {res.compute_us:.1f} us, stall "
                  f"{res.stall_us:.1f} us); host->HBM copies "
                  f"{sum(m[1] for m in reads)} B in "
                  f"{sum(m[2] for m in reads):.1f} us; bit-identical to "
                  f"oracle; rel L2 vs f32 ref {err:.3e} (bound {STREAM_RTOL})",
                  flush=True)
            check(err <= STREAM_RTOL, f"{kind} chain close to the f32 reference")
        finally:
            ex.engine.close()


def four_chip_phase(cfg, *, batch: int, seq: int, steps: int,
                    seed: int) -> None:
    step_cfg = TrainStepConfig(remat="full")
    opt_cfg = AdamWConfig(moment_style="bf16", decay_steps=steps)
    loop_cfg = LoopConfig(steps=steps, batch=batch, seq=seq, seed=seed,
                          log_every=steps + 1)
    devices = jax.devices()
    with use_mesh(make_mesh((2, 2), ("data", "model"), devices=devices)):
        sharded = train(cfg, step_cfg, opt_cfg, loop_cfg)
    spans = {len(leaf.sharding.device_set)
             for leaf in jax.tree.leaves(sharded.params)}
    check(spans == {len(devices)},
          f"every parameter spans {len(devices)} devices (got {spans})")
    with use_mesh(make_mesh((1, 1), ("data", "model"), devices=devices[:1])):
        single = train(cfg, step_cfg, opt_cfg, loop_cfg)
    a, b = np.asarray(sharded.losses), np.asarray(single.losses)
    err = float(np.max(np.abs(a - b) / np.abs(b)))
    print(f"four chips: {steps} steps, batch {batch} x seq {seq}; losses "
          f"mesh(2,2) {a.tolist()} one chip {b.tolist()}; max rel diff "
          f"{err:.3e} (bound {LOSS_RTOL}); mesh step times "
          f"{[round(t, 4) for t in sharded.step_times]} s, one chip "
          f"{[round(t, 4) for t in single.step_times]} s", flush=True)
    check(bool(np.isfinite(a).all()), "sharded losses are finite")
    check(err <= LOSS_RTOL, "sharded and one-chip losses agree")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for weights, prompts and chain data")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded training phase on 4 chips")
    args = ap.parse_args()

    n_chips = 4 if args.four_chips else 1
    dev = device_phase(n_chips)
    cache_dir = enable_compile_cache()
    print(f"compile cache: {cache_dir}", flush=True)
    meter = CompileMeter()
    t0 = time.perf_counter()

    if args.four_chips:
        cfg = dataclasses.replace(get_config("granite-8b"), n_layers=2)
        print(f"four chips: granite-8b widths, depth cut to {cfg.n_layers} "
              f"layers, bf16 moments, remat full", flush=True)
        four_chip_phase(cfg, batch=8, seq=512, steps=4, seed=args.seed)
        meter.report("four chips")
    else:
        cfg, params = load_model("granite-8b", full=True, seed=args.seed,
                                 n_layers=SERVE_LAYERS)
        print(f"serve: granite-8b d_model={cfg.d_model} heads={cfg.n_heads}"
              f"/{cfg.n_kv_heads} head_dim={cfg.head_dim} d_ff={cfg.d_ff} "
              f"vocab={cfg.vocab_size} {jnp.dtype(cfg.dtype).name}; depth cut "
              f"36 -> {cfg.n_layers} layers (36 need 16.2 GB, over 16 GB of "
              f"HBM)", flush=True)
        serve_phase(cfg, params, max_batch=8, max_len=2048, prompt_len=128,
                    new_tokens=32, waves=2, seed=args.seed)
        del params
        meter.report("serve")
        bf16 = jnp.bfloat16
        stream_phase({
            "matmul": matmul_chain(8, m=256, k=4096, dtype=bf16,
                                   seed=args.seed, block_m=256, block_n=256,
                                   block_k=512),
            "attention": attention_chain(4, seq=2048, heads=32, kv_heads=8,
                                         head_dim=128, dtype=bf16,
                                         seed=args.seed, block_q=512,
                                         block_k=512),
        })
        meter.report("stream")
    print(f"total: {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
