"""Run one cell of BENCHMARK.json on the chip and print its result.

    python3 chip_bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process: set-up (weights, corpus, index, engine, warm-up of every
shape the cell uses), then a window of `--seconds` driven by the cell's
traffic mix, then the check against the plain reference. The last line
of standard output is one JSON object: `correct`, `attempted`, `failed`,
`metrics` (the cell's end-to-end metrics with `--trace 0`, its per-layer
metrics with `--trace 1`), `device`, with `--trace 1` a `breakdown`,
and last `compared`: each number the check compared, with its limit.
The same comparisons are the last lines of standard error. A traced run
in which a per-layer metric declared for the cell reads nothing is not
correct (`missing_layer_metrics`).

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result. JAX's compilation cache is kept in the
checkout (`launch/compile_cache`), so only a cell's first run compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoDevice(RuntimeError):
    pass


def _log(msg: str) -> None:
    print(f"chip_bench: {msg}", file=sys.stderr, flush=True)


def _import_program(root: str) -> None:
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise FileNotFoundError(f"no program under {src}: run from a "
                                "checkout of the repository")
    if src not in sys.path:
        sys.path.insert(0, src)
    if root not in sys.path:
        sys.path.insert(0, root)


def devices_for(chips: int, require_tpu: bool) -> list:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX's default device is "
                       f"{devs[0].platform} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def peak_bytes(devs: list) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def _finite(x):
    return x if isinstance(x, (int, float)) and math.isfinite(x) else None


def layer_metrics(cell, drv, st, rec, events, peaks) -> tuple:
    """(per-layer metrics, device busy seconds, window seconds, breakdown).

    A metric that BENCHMARK.json declares for the cell and whose reader
    raises or finds nothing is left out of the metrics; the caller counts
    it against `correct`."""
    from chip_bench import spec, trace

    lo, hi = trace.window_of(events)
    dev_names = trace.devices(events) or ["none"]
    busy = [trace.busy_ns(events, d, lo, hi) for d in dev_names]
    ctx = {"events": events, "window": (lo, hi), "device": dev_names[0],
           "peaks": peaks,
           "host": drv.layer_context(st, rec), "cell": cell,
           "model": getattr(st, "model_cfg", None)}
    metrics = {}
    for m in cell.per_layer:
        try:
            value = spec.metric_reader(cell.root, m["name"])(ctx)
        except Exception as e:  # noqa: BLE001 - one reader must not sink all
            _log(f"metric {m['name']} failed: {e!r}")
            continue
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        else:
            _log(f"metric {m['name']} found nothing to read")
    ops = trace.device_ns_by_name(events, lo, hi)
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    gaps = trace.idle_by_span(events, dev_names[0], lo, hi)[:10]
    breakdown = {"device_ops": [[n, v / 1e9] for n, v in top],
                 "idle_gaps": [[n, v / 1e9] for n, v in gaps]}
    return metrics, sum(busy) / len(busy) / 1e9, (hi - lo) / 1e9, breakdown


def run_cell(cell, seed: int, seconds: float, traced: bool,
             require_tpu: bool = True, start: float = T_START,
             trace_dir: str | None = None) -> dict:
    """Set up, measure and check one cell; returns the result object."""
    _import_program(cell.root)
    import jax

    from chip_bench import spec, trace

    devs = devices_for(cell.chips, require_tpu)
    from chip_bench import peaks as peaks_mod

    kind = devs[0].device_kind
    peaks = peaks_mod.peaks_for(kind) if require_tpu \
        else peaks_mod.PEAKS.get(kind)
    from repro.launch.compile_cache import enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _log(f"compile cache: {enable_compile_cache()}")
    drv = spec.driver(cell.root, cell.traffic["driver"])
    st = drv.setup(cell, seed, seconds)
    setup_s = time.perf_counter() - start
    _log(f"set-up {setup_s:.3f} s")
    events = None
    if traced:
        with tempfile.TemporaryDirectory(dir=trace_dir) as tmp:
            with trace.profile(tmp):
                rec = drv.window(st, seconds, traced=True)
            events = trace.load_events(trace.find_xplane(tmp))
    else:
        rec = drv.window(st, seconds, traced=False)
    attempted, failed = drv.counts(rec)
    _log(f"window: {attempted} attempted, {failed} failed, "
         f"{rec.get('compiles_in_window', 0)} compilations in the window")
    memory = peak_bytes(devs)
    result = {"correct": False, "attempted": attempted, "failed": failed}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory}
    if traced:
        metrics, busy_s, window_s, breakdown = layer_metrics(
            cell, drv, st, rec, events, peaks)
        device.update(busy_s=busy_s, window_s=window_s)
    else:
        e2e = drv.end_to_end(st, rec)
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": _finite(e2e.get(m["name"])),
                               "unit": m["unit"]} for m in cell.end_to_end}
    t_check = time.perf_counter()
    drv.release(st)
    compared = drv.check(st, rec)
    _log(f"check {time.perf_counter() - t_check:.1f} s")
    if traced:
        missing = [m["name"] for m in cell.per_layer
                   if m["name"] not in metrics]
        if missing:
            _log(f"declared for this cell but not read: {missing}")
        compared.append(("missing_layer_metrics", float(len(missing)), 0.0))
    ok = all(math.isfinite(v) and v <= lim for _, v, lim in compared)
    ok = ok and failed == 0 and all(
        m["value"] is not None for m in metrics.values())
    result.update(correct=ok, metrics=metrics, device=device)
    if traced:
        result["breakdown"] = breakdown
    result["compared"] = {n: {"value": _finite(v), "limit": lim}
                          for n, v, lim in compared}
    for n, v, lim in compared:
        _log(f"compared {n} {v!r} limit {lim!r}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from chip_bench import spec

    try:
        cell = spec.resolve(args.workload, ROOT)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except (NoDevice, FileNotFoundError, KeyError) as e:
        _log(f"FAILED: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
