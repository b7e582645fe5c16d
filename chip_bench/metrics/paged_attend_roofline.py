"""Kernel: the paged-attention kernel's share of its roofline (%).

Its analytic FLOPs and bytes (KV read, new KV, q and o; chip_bench/costs)
of every call in the window, each bounded by the larger of FLOPs over the
bf16 peak and bytes over HBM bandwidth, over the summed device time of
the ops named for the kernel in the trace. Nothing to read when no op of
that name ran (the gather path)."""
from chip_bench import costs, trace

KERNEL = "paged_attend"


def read(ctx):
    if ctx["peaks"] is None:
        return None
    lo, hi = ctx["window"]
    ns = sum(trace.device_ns_by_name(ctx["events"], lo, hi, KERNEL).values())
    work = ctx["host"].get("paged_attend_work")
    if not ns or not work:
        return None
    p = ctx["peaks"]
    return costs.roofline_share(work, ns / 1e9, p.bf16_flops, p.hbm_bw)
