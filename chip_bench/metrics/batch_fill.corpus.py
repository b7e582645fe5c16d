"""Scheduler: mean rows per flush in the window over `max_batch` (%), from
the scheduler's own counters (`AsyncBatchScheduler.stats()`) read at the
window's edges. Padding rows are not counted."""


def read(ctx):
    h = ctx["host"]
    s0, s1 = h.get("sched0"), h.get("sched1")
    if not s0 or not s1:
        return None
    flushes = s1["n_flushes"] - s0["n_flushes"]
    if flushes <= 0:
        return None
    return 100.0 * (s1["n_served"] - s0["n_served"]) / flushes \
        / h["max_batch"]
