"""Model step: mean device time of a decode step (ms), from the trace."""
import numpy as np

from chip_bench import trace


def read(ctx):
    ms = trace.launch_device_ms(ctx["events"], "cb.decode", *ctx["window"])
    return float(np.mean(ms)) if ms else None
