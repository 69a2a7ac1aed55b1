"""Read the numbers that ``correct`` compares, for the program and for its
controls, over many seeds in one process (set-up is paid once per seed,
start-up once). This is how each limit in ``configs/*.json`` was set.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10 \
        [--controls int8,fp8]

Prints one JSON line per seed: the program's readings and, for each control
(the reference with its weights rounded to that precision, in the program's
place), the same number read at the same positions.
"""
from __future__ import annotations

import argparse
import json
import time

import run as harness


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", default="")
    args = ap.parse_args()
    spec, cell = harness.cell_spec(args.workload)
    cfg = json.loads((harness.BENCH / "configs" / f"{cell['config']}.json").read_text())
    traffic = json.loads(
        (harness.BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    import jax
    from common import load_module
    _dev, _devices, peaks = harness.check_device(jax, cell["chips"])
    harness.enable_compile_cache(jax)
    driver = load_module(harness.BENCH / "drivers" / f"{traffic['driver']}.py")
    controls = [c for c in args.controls.split(",") if c]
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        run = driver.Run(cfg, traffic, seed, peaks)
        run.setup()
        run.window(args.seconds)
        e2e = run.end_to_end()
        run.release()
        t1 = time.perf_counter()
        readings = run.readings(controls)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "readings": readings, "end_to_end": e2e,
                          "run_s": t1 - t0,
                          "check_s": time.perf_counter() - t1}), flush=True)
        del run


if __name__ == "__main__":
    main()
