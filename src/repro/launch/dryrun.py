import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax-importing import: jax locks the device count at
# first backend init. (This also forces this module's docstring below the
# env setup, hence the plain-string doc.)

DOC = """Multi-pod dry-run: lower + compile EVERY (arch x shape x mesh) cell.

For each cell:
  1. `.lower().compile()` the real program (scan-over-layers) on the
     production mesh -> proves the sharding config is coherent; records
     `memory_analysis()` (does it fit 16 GiB/chip?) and the HLO
     collective schedule.
  2. (single-pod only) lower two reduced-layer UNROLLED minis and
     linearly extrapolate trip-count-exact FLOPs / bytes / collective
     bytes for the roofline table (see repro.perf.roofline docstring).

Results land in experiments/dryrun/<arch>__<shape>__<mesh>.json and are
consumed by benchmarks/roofline tooling and EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                  # everything
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-34b \
      --shape train_4k --mesh single                            # one cell
"""


import argparse
import dataclasses
import gzip
import json
import time
import traceback

import jax

from repro.configs import SHAPES, all_archs, get_config, shape_applicable
from repro.perf import roofline
from .mesh import make_production_mesh, mesh_chips
from .steps import lower_cell

OUT_DIR = "experiments/dryrun"


def _unit_layers(cfg, units: int):
    """Reduced-layer mini config of `units` scaling units."""
    ch = {"n_layers": units, "scan_unroll": True, "grad_accum_steps": 1}
    if cfg.family == "hybrid":
        ch["n_layers"] = units * cfg.hybrid_attn_every
    if cfg.encoder is not None:
        ch["encoder"] = dataclasses.replace(cfg.encoder, n_layers=units)
    return dataclasses.replace(cfg, **ch)


def n_units(cfg) -> int:
    if cfg.family == "hybrid":
        return cfg.n_layers // cfg.hybrid_attn_every
    return cfg.n_layers


def _mini_cfg(cfg, shape, units: int):
    mini = _unit_layers(cfg, units)
    if shape.seq_len >= 32_768 and mini.attn_chunk:
        # fewer, fatter attention chunks: same FLOPs/bytes, 64 unrolled
        # bodies instead of 1024
        mini = dataclasses.replace(mini, attn_chunk=shape.seq_len // 8)
    return mini


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = OUT_DIR, save_hlo: bool = False,
             force: bool = False) -> dict:
    mesh_name = "multipod" if multi_pod else "pod"
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_name}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "kind": shape.kind, "status": "ok"}

    ok, why = shape_applicable(cfg, shape)
    if not ok:
        rec.update(status="skipped", reason=why)
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        return rec

    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        chips = mesh_chips(mesh)
        t0 = time.time()
        lowered = lower_cell(cfg, mesh, shape)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        ma = compiled.memory_analysis()
        raw = roofline.analyze_compiled(compiled)
        rec.update(
            chips=chips,
            lower_s=round(t1 - t0, 2),
            compile_s=round(t2 - t1, 2),
            memory={
                "args_gib": ma.argument_size_in_bytes / 2**30,
                "temp_gib": ma.temp_size_in_bytes / 2**30,
                "output_gib": ma.output_size_in_bytes / 2**30,
                "fits_16gib": (ma.argument_size_in_bytes
                               + ma.temp_size_in_bytes)
                < roofline.peaks_for(roofline.DRYRUN_DEVICE_KIND).hbm_bytes,
            },
            raw_cost=raw,
        )
        if save_hlo:
            hlo_path = path.replace(".json", ".hlo.gz")
            with gzip.open(hlo_path, "wt") as f:
                f.write(compiled.as_text())
            rec["hlo"] = hlo_path

        if not multi_pod:
            # roofline minis (single-pod only, per the spec)
            minis = []
            for u in (1, 2):
                mini = _mini_cfg(cfg, shape, u)
                ml = lower_cell(mini, mesh, shape)
                minis.append(roofline.analyze_compiled(ml.compile()))
            corrected = roofline.extrapolate(minis[0], minis[1],
                                             n_units(cfg))
            cell = roofline.CellAnalysis(
                flops=corrected["flops"],
                hbm_bytes=corrected["bytes"],
                collective_bytes=corrected["collective_bytes"],
                collectives=corrected["collectives"],
                memory_args_bytes=ma.argument_size_in_bytes,
                memory_temp_bytes=ma.temp_size_in_bytes,
                memory_output_bytes=ma.output_size_in_bytes,
            )
            mf = roofline.model_flops(cfg, shape)
            af = roofline.attention_flops(cfg, shape)
            rec["roofline"] = cell.to_dict()
            rec["roofline"]["model_flops_global"] = mf
            rec["roofline"]["model_flops_per_device"] = mf / chips
            rec["roofline"]["useful_flops_ratio"] = (
                mf / chips / max(cell.flops, 1.0))
            rec["roofline"]["attn_flops_global"] = af
            rec["roofline"]["useful_flops_ratio_attn_adj"] = (
                (mf + af) / chips / max(cell.flops, 1.0))
    except Exception as e:  # noqa: BLE001 — record, don't crash the sweep
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description=DOC)
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default=OUT_DIR)
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = all_archs() if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    t0 = time.time()
    n_ok = n_skip = n_err = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape, mp, args.out,
                               save_hlo=args.save_hlo, force=args.force)
                tag = f"{arch:22s} {shape:12s} {'2x16x16' if mp else '16x16':8s}"
                if rec["status"] == "ok":
                    n_ok += 1
                    mem = rec["memory"]
                    rf = rec.get("roofline", {})
                    extra = (f" bottleneck={rf['bottleneck']:10s}" if rf
                             else "")
                    print(f"OK   {tag} compile={rec.get('compile_s', 0):6.1f}s"
                          f" temp={mem['temp_gib']:7.2f}GiB"
                          f" fits={mem['fits_16gib']}{extra}", flush=True)
                elif rec["status"] == "skipped":
                    n_skip += 1
                    print(f"SKIP {tag} {rec['reason'][:70]}", flush=True)
                else:
                    n_err += 1
                    print(f"ERR  {tag} {rec['error'][:120]}", flush=True)
    print(f"\ndone in {time.time()-t0:.0f}s: {n_ok} ok, {n_skip} skipped, "
          f"{n_err} errors")


if __name__ == "__main__":
    main()
