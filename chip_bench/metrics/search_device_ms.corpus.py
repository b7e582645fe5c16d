"""Index: device time per search flush (ms), from the trace: the programs
that each of the harness's `cb.search` spans enqueued, found by their run
ids, summed per span and averaged over the window's spans."""
import numpy as np

from chip_bench import trace


def read(ctx):
    ms = trace.span_device_ms(ctx["events"], "cb.search", *ctx["window"])
    return float(np.mean(ms)) if ms else None
