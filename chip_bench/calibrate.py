"""Readings that the check's limits are set from, many seeds in one process.

    python3 chip_bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --seconds <s> [--control 1] [--rates <requests/s>,...]

For each seed: the cell's set-up, a window of `--seconds`, the check's
numbers (the program's readings), and with `--control 1` the control's
readings on the same sample: the reference in the next lower precision
put in the program's place (fp8 matmuls for the bf16 model). One JSON
line per seed on standard output. `--rates` runs each
seed at each of these open-loop rates instead of the mix's own, with
the driver's backlog readout: the sweep that finds a cell's knee
(`--check 0` skips the reference there). Not a
benchmark run: the benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def readings(cell, seed: int, seconds: float, control: bool,
             require_tpu: bool = True, check: bool = True) -> dict:
    sys.path.insert(0, cell.root)
    from chip_bench import run, spec

    run._import_program(cell.root)
    devs = run.devices_for(cell.chips, require_tpu)
    drv = spec.driver(cell.root, cell.traffic["driver"])
    t0 = time.perf_counter()
    st = drv.setup(cell, seed, seconds)
    setup_s = time.perf_counter() - t0
    rec = drv.window(st, seconds, traced=False)
    e2e = drv.end_to_end(st, rec)
    if hasattr(drv, "backlog"):
        e2e.update(drv.backlog(rec))
    attempted, failed = drv.counts(rec)
    memory = run.peak_bytes(devs)
    drv.release(st)
    out = {"seed": seed, "attempted": attempted, "failed": failed,
           "setup_s": setup_s, "memory_peak_bytes": memory,
           "compiles_in_window": rec.get("compiles_in_window"),
           "end_to_end": e2e}
    if check:
        out["program"] = {n: v for n, v, _ in drv.check(st, rec)}
    if control:
        out["control"] = drv.control(st, rec)
    del st, rec
    gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--rates", default=None)
    ap.add_argument("--check", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from chip_bench import run, spec

    cell = spec.resolve(args.workload, ROOT)
    rates = [None] if args.rates is None else [
        float(r) for r in args.rates.split(",")]
    run._import_program(ROOT)
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        for rate in rates:
            if rate is not None:
                cell.traffic["rate_per_s"] = rate
            out = readings(cell, seed, args.seconds, bool(args.control),
                           check=bool(args.check))
            out["rate_per_s"] = cell.traffic.get("rate_per_s")
            print(json.dumps(out, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
