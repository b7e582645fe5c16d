"""Index programs (score and top-k): the searches' share of their roofline
(%). Each flush's least time is the larger of its int8 operations over
the int8 peak and its bytes (codes, norms, queries) over HBM bandwidth
(chip_bench/scan_cost.py, from the corpus and the padded batch alone);
summed over the window's traced flushes, over the device time of the
programs their `cb.search` spans enqueued."""
from chip_bench import scan_cost, trace


def read(ctx):
    work = ctx["host"].get("search_work")
    if ctx["peaks"] is None or work is None:
        return None
    ms = trace.span_device_ms(ctx["events"], "cb.search", *ctx["window"])
    if not ms:
        return None
    p = ctx["peaks"]
    least = scan_cost.least_seconds(work, p.int8_ops, p.hbm_bw)
    return 100.0 * least * len(ms) / (sum(ms) / 1e3)
