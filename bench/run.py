"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration file
(``bench/configs/<config>.json``) and traffic file
(``bench/traffic/<traffic>.json``) are found by name, the traffic file names
its driver (``bench/drivers/<driver>.py``), and each per-layer metric is
read by ``bench/metrics/<metric>.py``. Set-up (import, weights, compile or
cache load, warm-up) runs first; then the window runs for ``--seconds``;
then the device's peak memory is read, the program's state is freed and the
outputs of the window are compared with the plain reference.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the metrics are the
per-layer ones. The run refuses to measure without a TPU whose device kind
is in the peaks table, or with fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]
# the TPU runtime logs to /tmp/tpu_logs unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

#: the persistent compile cache, at a fixed path inside the checkout
CACHE_DIR = ROOT / ".jax_cache"
#: where a traced run writes its profile (replaced on every traced run)
TRACE_DIR = ROOT / ".bench_trace"


def fail(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def cell_spec(name: str) -> tuple[dict, dict]:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        if w["name"] == name:
            return spec, w
    fail(f"no workload {name!r} in BENCHMARK.json")


def applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def enable_compile_cache(jax) -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set,
    else ``<checkout>/.jax_cache``; every program is cached, however fast it
    compiled."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def check_device(jax, chips: int):
    """The device, or exit non-zero: no TPU, an unknown kind, too few chips."""
    from peaks import peaks_for
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: jax.devices()[0] is {dev.platform!r} "
             f"({dev.device_kind!r}); the benchmark measures only on a TPU")
    try:
        peaks = peaks_for(dev.device_kind)
    except KeyError as e:
        fail(str(e.args[0]))
    if len(devices) < chips:
        fail(f"the cell needs {chips} chip(s), JAX sees {len(devices)}")
    return dev, devices[:chips], peaks


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    spec, cell = cell_spec(args.workload)
    cfg = json.loads((BENCH / "configs" / f"{cell['config']}.json").read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())

    import jax
    dev, devices, peaks = check_device(jax, cell["chips"])
    cache = enable_compile_cache(jax)
    log(f"device: {dev.platform} {dev.device_kind!r} x{len(devices)}; "
        f"compile cache {cache}")
    result = measure(spec, cell, cfg, traffic, args.seed, args.seconds,
                     args.trace, devices, peaks)
    print(json.dumps(result), flush=True)


def measure(spec: dict, cell: dict, cfg: dict, traffic: dict, seed: int,
            seconds: float, trace: int, devices: list, peaks: dict) -> dict:
    """Set-up, window, metrics and check of one run; returns the result line.
    The numbers compared are logged last, each beside its limit."""
    import jax
    from common import CompileMeter, load_module
    dev = devices[0]
    meter = CompileMeter()
    driver = load_module(BENCH / "drivers" / f"{traffic['driver']}.py")
    run = driver.Run(cfg, traffic, seed, peaks)
    run.setup()
    setup_s = time.perf_counter() - T_START
    at_setup = meter.snapshot()
    log(f"set-up: {setup_s:.3f} s; {json.dumps(run.info)}; compile "
        f"{at_setup['compile_s']:.3f} s in {at_setup['compiles']} programs, "
        f"cache hits {at_setup['cache_hits']} misses {at_setup['cache_misses']}")

    if trace:
        from jax.profiler import ProfileOptions
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench:window"):
        run.window(seconds)
    if trace:
        jax.profiler.stop_trace()
    in_window = meter.snapshot()["compiles"] - at_setup["compiles"]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    log(f"window: {run.t1 - run.t0:.3f} s, programs compiled inside it "
        f"{in_window}, peak device memory {peak} B")

    metrics, breakdown, extra_device = {}, None, {}
    units = {m["name"]: m["unit"] for m in spec["end_to_end"] + spec["per_layer"]}
    if trace:
        import trace_reduce
        rec = run.record()
        rec["trace"] = trace_reduce.reduce(trace_reduce.find_xplane(str(TRACE_DIR)))
        extra_device = {"busy_s": rec["trace"]["busy_s"],
                        "window_s": rec["trace"]["window_s"]}
        breakdown = {"device_ops": rec["trace"]["device_ops"],
                     "idle_gaps": rec["trace"]["idle_gaps"]}
        for m in spec["per_layer"]:
            if applies(m, cell["name"]):
                reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
                value = reader.read(rec)
                if value is not None:
                    metrics[m["name"]] = value
    else:
        e2e = run.end_to_end()
        e2e.update(setup_s=setup_s, peak_hbm_gb=peak / 1e9)
        for m in spec["end_to_end"]:
            if applies(m, cell["name"]):
                metrics[m["name"]] = e2e[m["name"]]

    run.release()
    checks = run.check()
    correct = all(ok for _v, _l, ok in checks.values())
    for name, (value, limit, ok) in checks.items():
        log(f"check {name}: {value!r} limit {limit!r} {'ok' if ok else 'FAIL'}")
    result = {
        "correct": bool(correct),
        "attempted": run.attempted(), "failed": run.failed(),
        "metrics": {k: {"value": v, "unit": units[k]} for k, v in metrics.items()},
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": peak,
                   **extra_device},
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim, _ok) in checks.items()}
    return result


if __name__ == "__main__":
    main()
