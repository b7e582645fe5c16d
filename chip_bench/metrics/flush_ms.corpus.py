"""Index, seen from the scheduler: median host duration of the harness's
`cb.search` spans in the window (ms): a flush's queries gathered and
padded, the search dispatched, its ids and scores back on the host."""
import numpy as np


def read(ctx):
    lo, hi = ctx["window"]
    ms = [s["dur"] / 1e6 for s in ctx["events"]["spans"]
          if s["name"] == "cb.search" and lo <= s["start"] <= hi]
    return float(np.median(ms)) if ms else None
