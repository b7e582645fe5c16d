"""Client: how late the open-loop generator sent requests, p99 (ms).

Host clock: submit time minus due time, over the window's requests. A
starved client would read as a fast server; this guards against it."""
import numpy as np


def read(ctx):
    lag = ctx["host"].get("lag_ms")
    return float(np.percentile(lag, 99)) if lag is not None and len(lag) \
        else None
