"""Scheduler: retrieval wait (submit to served) of the window's requests,
p90 (ms), from each `AsyncTicket.wait_s` (host clock)."""
import numpy as np


def read(ctx):
    w = ctx["host"].get("retrieval_wait_ms")
    return float(np.percentile(w, 90)) if w is not None and len(w) else None
