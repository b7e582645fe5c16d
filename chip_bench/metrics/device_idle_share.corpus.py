"""Device: share of the traced window in which no op ran on the chip (%):
1 - busy / window, busy being the union of the device's op intervals."""
from chip_bench import trace


def read(ctx):
    lo, hi = ctx["window"]
    busy = trace.busy_ns(ctx["events"], ctx["device"], lo, hi)
    return 100.0 * (1.0 - busy / (hi - lo)) if hi > lo else None
