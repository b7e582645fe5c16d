"""Device: the searches' int8 operations (two per query, document and
dimension; chip_bench/scan_cost.py) over their device time times the
chip's int8 peak (%): the whole search's share of the peak, whatever
programs it runs."""
from chip_bench import trace


def read(ctx):
    work = ctx["host"].get("search_work")
    if ctx["peaks"] is None or work is None:
        return None
    ms = trace.span_device_ms(ctx["events"], "cb.search", *ctx["window"])
    if not ms:
        return None
    return 100.0 * work[0] * len(ms) / (sum(ms) / 1e3
                                        * ctx["peaks"].int8_ops)
