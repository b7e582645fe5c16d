"""Roofline-term derivation from compiled dry-run artifacts.

Hardware model: per-chip peaks from `PEAKS`, keyed by the `device_kind`
JAX reports. A device missing from the table is an error, never a
default (see `peaks_for`).

Terms (seconds, PER STEP, computed from per-device quantities — the
"/ chips" in the spec formulas cancels because the SPMD program IS the
per-device program):
    compute_s    = HLO_FLOPs_per_device    / peak bf16 FLOP/s
    memory_s     = HLO_bytes_per_device    / peak HBM bytes/s
    collective_s = collective_bytes_per_device / per-link ICI bytes/s

## The scan-trip-count correction (IMPORTANT)

XLA's HloCostAnalysis counts a while-loop body ONCE, ignoring the trip
count, and our models scan over layers — so cost_analysis() of the real
program undercounts by ~the layer count. We therefore lower each cell a
further TWO times with a reduced layer count (1 and 2 "units") and all
scans UNROLLED (`cfg.scan_unroll`), which makes the analysis exact, and
extrapolate linearly:

    per_unit = A(2u) - A(1u);  base = A(1u) - per_unit
    total    = base + n_units * per_unit

A "unit" is one layer (dense/moe/ssm/vlm), one SSM-group+shared-block
(zamba2), or one encoder+one decoder layer (whisper). Microbatching is
disabled in the minis (it only rescales the same total FLOPs through
another scan). This is exact for FLOPs/collectives (layers are
homogeneous) and a faithful accounting for bytes.

Collective bytes are parsed from the optimized HLO: every all-reduce /
all-gather / reduce-scatter / all-to-all / collective-permute output
shape, with an all-reduce counted 2x (ring reduce-then-broadcast moves
2(n-1)/n ~= 2 bytes per byte reduced).
"""
from __future__ import annotations

import dataclasses
import re


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float       # bf16 FLOP/s
    hbm_bw: float      # HBM bytes/s
    hbm_bytes: int     # HBM capacity
    link_bw: float     # ICI bytes/s per link


# Source: Google Cloud TPU documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s inter-chip
# interconnect over four links (~50 GB/s per link).
PEAKS = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9,
                             hbm_bytes=16 * 2**30, link_bw=50e9),
}
# The chip the dry-run cells are compiled for (`launch.dryrun`).
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks_for(device_kind: str) -> ChipPeaks:
    """Per-chip peaks for a JAX `device_kind`; raises for any other."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak table entry for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
             "collective-permute")
# effective bytes-moved multiplier per output byte
_COLL_WEIGHT = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
                "all-to-all": 1.0, "collective-permute": 1.0}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(tok_dtype: str, dims: str) -> int:
    n = _DTYPE_BYTES.get(tok_dtype)
    if n is None:
        return 0
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def parse_collectives(hlo_text: str) -> dict:
    """Sum output-shape bytes of every collective op in the (per-device)
    optimized HLO. Returns {op: {"count": int, "bytes": int}, "total": b}.

    NOTE: bodies of while loops appear once — use the unrolled minis for
    trip-count-correct numbers (see module docstring).
    """
    out = {op: {"count": 0, "bytes": 0} for op in _COLL_OPS}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(r"(?:ROOT )?%?[\w.\-]+ = (.+?) (all-reduce|all-gather|"
                     r"reduce-scatter|all-to-all|collective-permute)"
                     r"(?:-start|-done)?\(", stripped)
        if not m:
            continue
        lhs, op = m.group(1), m.group(2)
        if "-done(" in stripped and op != "collective-permute":
            # *-done carries the same shape as *-start: count once (start)
            continue
        nbytes = sum(_shape_bytes(d, dims)
                     for d, dims in _SHAPE_RE.findall(lhs))
        out[op]["count"] += 1
        out[op]["bytes"] += int(nbytes * _COLL_WEIGHT[op])
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    return out


@dataclasses.dataclass
class CellAnalysis:
    flops: float                 # per device, trip-count-corrected
    hbm_bytes: float
    collective_bytes: float
    collectives: dict
    memory_args_bytes: int = 0   # from the REAL (full) program
    memory_temp_bytes: int = 0
    memory_output_bytes: int = 0

    @property
    def peaks(self) -> ChipPeaks:
        return peaks_for(DRYRUN_DEVICE_KIND)

    @property
    def compute_s(self) -> float:
        return self.flops / self.peaks.flops

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / self.peaks.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / self.peaks.link_bw

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """No-overlap upper bound; perfect-overlap lower bound is max."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    def to_dict(self) -> dict:
        return {
            "flops_per_device": self.flops,
            "hbm_bytes_per_device": self.hbm_bytes,
            "collective_bytes_per_device": self.collective_bytes,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "collectives": self.collectives,
            "memory_args_gib": self.memory_args_bytes / 2**30,
            "memory_temp_gib": self.memory_temp_bytes / 2**30,
            "memory_output_gib": self.memory_output_bytes / 2**30,
        }


def analyze_compiled(compiled) -> dict:
    """Raw (uncorrected) analysis of one compiled program."""
    ca = compiled.cost_analysis()
    txt = compiled.as_text()
    colls = parse_collectives(txt)
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "collective_bytes": float(colls["total_bytes"]),
        "collectives": colls,
    }


def extrapolate(a1: dict, a2: dict, n_units: int) -> dict:
    """Linear extrapolation base + n_units * per_unit from 1u/2u minis."""
    out = {}
    for k in ("flops", "bytes", "collective_bytes"):
        per = max(a2[k] - a1[k], 0.0)
        base = max(a1[k] - per, 0.0)
        out[k] = base + n_units * per
    # collective op-counts: scale counts the same way for reporting
    coll = {}
    for op in _COLL_OPS:
        c1 = a1["collectives"][op]
        c2 = a2["collectives"][op]
        per_b = max(c2["bytes"] - c1["bytes"], 0)
        per_c = max(c2["count"] - c1["count"], 0)
        coll[op] = {
            "bytes": int(max(c1["bytes"] - per_b, 0) + n_units * per_b),
            "count": int(max(c1["count"] - per_c, 0) + n_units * per_c),
        }
    out["collectives"] = coll
    return out


def model_flops(cfg, shape) -> float:
    """Analytic MODEL_FLOPS: 6*N*D train, 2*N*D forward-only (prefill),
    2*N_active*D for MoE; decode D = batch tokens (one step)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    tokens = shape.global_batch  # one new token per sequence
    return 2.0 * n * tokens


def attention_flops(cfg, shape) -> float:
    """Intrinsic attention-score flops (NOT in 6*N*D): per token pair
    2*hd (QK^T) + 2*hd (PV) per head; causal halves it; x3 for train bwd.
    Zero for attention-free archs; hybrid counts the shared block only."""
    heads = getattr(cfg, "effective_n_heads", cfg.n_heads)
    if not heads:
        return 0.0
    hd = cfg.resolved_head_dim
    b, s = shape.global_batch, shape.seq_len
    if cfg.family == "hybrid":
        n_attn = cfg.n_layers // max(cfg.hybrid_attn_every, 1)
    elif cfg.family == "audio":
        e = cfg.encoder
        enc = e.n_layers * 4 * b * e.n_heads * e.n_frames**2 *             (e.d_model // e.n_heads)
        dec = cfg.n_layers * (2 * b * heads * s * s * hd  # causal self
                              + 4 * b * heads * s * e.n_frames * hd)
        base = enc + dec
        return 3.0 * base if shape.kind == "train" else base
    else:
        n_attn = cfg.n_layers
    if cfg.family != "audio":
        base = n_attn * 2.0 * b * heads * s * s * hd  # causal: 4*s^2/2
    if shape.kind == "train":
        return 3.0 * base
    if shape.kind == "prefill":
        return base
    # decode: one query against the cache
    return n_attn * 4.0 * b * heads * s * hd
