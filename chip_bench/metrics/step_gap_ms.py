"""Engine: median gap between consecutive step programs on the device
(ms), from the trace: the prefill and decode launches of the window."""
import numpy as np

from chip_bench import trace


def read(ctx):
    gaps = trace.launch_gaps_ms(ctx["events"], trace.STEP_KINDS,
                                *ctx["window"])
    return float(np.median(gaps)) if gaps else None
