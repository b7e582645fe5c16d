"""Profiler traces: recording, loading, and the reduction to metrics.

Recording: `profile(dir)` wraps `jax.profiler` with the Python tracer off
(it would add an event per Python call), and `span(name)` writes a host
span (`jax.profiler.TraceAnnotation`) into the same trace. The harness
names its spans `cb.<what>`.

Loading: `load_events(path)` reads an `.xplane.pb` with nothing but JAX
and keeps what the reductions need, as plain lists of dicts:

  device     every op on a device plane's op line: name, start, dur (ns),
             device, module, program_id, run_id
  modules    every program execution on a device: name, start, dur,
             device, run_id
  spans      every host span named `cb.*`: name, start, dur, thread
  launches   host events that carry a `run_id`: name, start, dur, thread,
             run_id; the earliest of a run id is the program's enqueue,
             often made on a runtime thread after the dispatch returned

The device keeps its own clock; `align` puts it on the host's. A span
that waits for its own programs (a search) owns what it enqueued, found
by run id with no clock in between. The engine's step spans only
dispatch: the enqueue comes later on a runtime thread, so each is
matched, in order, to the first execution that starts after it.

Reductions (all times in ns on the trace's clock):

  busy_ns        union of the op intervals of one device in a window
  idle_gaps      the gaps of that union, each labelled with the innermost
                 `cb.*` span open at the gap's midpoint
  span_device_ms device time of the programs a synchronous span enqueued
  launches       each asynchronous launch span matched, in order, to the
                 program execution it started
  launch_device_ms  device time of each launch's program
  launch_gaps_ms gaps between consecutive executions of launched programs
  device_ns_by_name summed time of leaf ops by name
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
from collections import defaultdict

SPAN_PREFIX = "cb."
DEVICE_PLANE_PREFIX = "/device:"
OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


@contextlib.contextmanager
def profile(log_dir: str):
    """Trace the enclosed block into `log_dir` (host spans, device ops)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def span(name: str):
    """A host span in the profiler's trace; nearly free when not tracing."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _stats(ev) -> dict:
    out = {}
    for item in ev.stats:
        try:
            k, v = item
        except (TypeError, ValueError):
            continue
        out[k] = v
    return out


def describe(path: str, n_events: int = 3) -> list:
    """Planes, lines and a few events of each: for reading a trace by hand."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            lines.append({
                "line": line.name, "n": len(evs),
                "first": [{"name": e.name, "start": e.start_ns,
                           "dur": e.duration_ns,
                           "stats": {k: str(v)[:80]
                                     for k, v in _stats(e).items()}}
                          for e in evs[:n_events]]})
        out.append({"plane": plane.name, "lines": lines})
    return out


def load_events(path: str) -> dict:
    """The normalized event lists described in the module docstring."""
    from jax.profiler import ProfileData

    device, modules, spans, launches = [], [], [], []
    for plane in ProfileData.from_file(path).planes:
        is_device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            if is_device and line.name in OPS_LINES:
                for e in line.events:
                    st = _stats(e)
                    device.append({
                        "name": e.name, "start": float(e.start_ns),
                        "dur": float(e.duration_ns), "device": plane.name,
                        "module": str(st.get("hlo_module", "")),
                        "program_id": st.get("program_id"),
                        "run_id": st.get("run_id")})
            elif is_device and line.name in MODULE_LINES:
                for e in line.events:
                    st = _stats(e)
                    modules.append({
                        "name": e.name, "start": float(e.start_ns),
                        "dur": float(e.duration_ns), "device": plane.name,
                        "run_id": st.get("run_id")})
            elif not is_device:
                thread = f"{plane.name}/{line.name}"
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append({"name": e.name,
                                      "start": float(e.start_ns),
                                      "dur": float(e.duration_ns),
                                      "thread": thread})
                        continue
                    st = _stats(e)
                    if "run_id" in st:
                        launches.append({"name": e.name,
                                         "start": float(e.start_ns),
                                         "dur": float(e.duration_ns),
                                         "thread": thread,
                                         "run_id": st["run_id"]})
    return align({"device": device, "modules": modules, "spans": spans,
                  "launches": launches})


def enqueues(events: dict) -> dict:
    """run id -> host time its program was enqueued: the earliest host
    event that carries the run id (the enqueue precedes the completion)."""
    out: dict = {}
    for ln in events["launches"]:
        r = ln["run_id"]
        out[r] = min(out.get(r, float("inf")), ln["start"])
    return out


def align(events: dict) -> dict:
    """Put device events on the host clock.

    No program starts on the device before the host enqueued it, and one
    enqueued on an idle device starts within microseconds. So the least
    (device start - host enqueue) over the run ids is the offset between
    the clocks, subtracted from every device event."""
    enq = enqueues(events)
    d = [m["start"] - enq[m["run_id"]] for m in events["modules"]
         if m["run_id"] in enq]
    offset = min(d) if d else 0.0
    if offset:
        for key in ("device", "modules"):
            for e in events[key]:
                e["start"] -= offset
    events["offset"] = offset
    return events


# ------------------------------------------------------------ reductions
def window_of(events: dict, name: str = "cb.window") -> tuple:
    """(start, end) of the first span called `name`."""
    for s in events["spans"]:
        if s["name"] == name:
            return s["start"], s["start"] + s["dur"]
    raise ValueError(f"no {name!r} span in the trace")


def devices(events: dict) -> list:
    return sorted({e["device"] for e in events["device"]})


def merged_intervals(events: dict, device: str, lo: float, hi: float) -> list:
    """Union of the op intervals of `device`, clipped to [lo, hi]."""
    iv = sorted((max(e["start"], lo), min(e["start"] + e["dur"], hi))
                for e in events["device"] if e["device"] == device)
    out: list = []
    for a, b in iv:
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_ns(events: dict, device: str, lo: float, hi: float) -> float:
    return sum(b - a for a, b in merged_intervals(events, device, lo, hi))


def idle_gaps(events: dict, device: str, lo: float, hi: float) -> list:
    """[(label, gap ns)] for every gap in the device's busy union inside
    [lo, hi], labelled by the innermost (latest-starting) `cb.*` span
    other than the window that is open at the gap's midpoint."""
    iv = merged_intervals(events, device, lo, hi)
    edges = [lo] + [x for a, b in iv for x in (a, b)] + [hi]
    gaps = [((a + b) / 2, b - a) for a, b in zip(edges[::2], edges[1::2])
            if b > a]
    spans = sorted((s["start"], s["start"] + s["dur"], s["name"])
                   for s in events["spans"] if s["name"] != "cb.window")
    out, active, i = [], [], 0
    for mid, gap in gaps:                       # midpoints are increasing
        while i < len(spans) and spans[i][0] <= mid:
            active.append(spans[i])
            i += 1
        active = [s for s in active if s[1] >= mid]
        out.append((active[-1][2] if active else "no span", gap))
    return out


def idle_by_span(events: dict, device: str, lo: float, hi: float) -> list:
    """Idle time summed by label, largest first."""
    tot: dict = defaultdict(float)
    for label, gap in idle_gaps(events, device, lo, hi):
        tot[label] += gap
    return sorted(tot.items(), key=lambda kv: -kv[1])


def span_executions(events: dict, kind: str, lo: float, hi: float) -> list:
    """One list per `kind` span starting in [lo, hi]: the program
    executions enqueued (by run id) while the span was open, for a span
    that waits for its own programs, so that they are enqueued inside it.

    Spans of other threads overlap: a program counts only if at least 80%
    of its executions were enqueued inside `kind` spans, so what another
    thread enqueued meanwhile is left out."""
    enq = enqueues(events)
    by_run: dict = defaultdict(list)
    total: dict = defaultdict(int)
    for m in events["modules"]:
        by_run[m["run_id"]].append(m)
        total[m["name"]] += 1
    order = sorted((t, r) for r, t in enq.items())
    times = [t for t, _ in order]
    spans = sorted((s for s in events["spans"] if s["name"] == kind),
                   key=lambda s: s["start"])
    inside = []
    hits: dict = defaultdict(int)
    for s in spans:
        a = bisect.bisect_left(times, s["start"])
        b = bisect.bisect_right(times, s["start"] + s["dur"])
        ms = [m for _, r in order[a:b] for m in by_run.get(r, ())]
        inside.append(ms)
        for m in ms:
            hits[m["name"]] += 1
    own = {n for n, c in hits.items() if c >= 0.8 * total[n]}
    return [[m for m in ms if m["name"] in own]
            for s, ms in zip(spans, inside) if lo <= s["start"] <= hi]


def span_device_ms(events: dict, kind: str, lo: float, hi: float) -> list:
    """Device ms of the programs each `kind` span in [lo, hi] enqueued;
    spans that enqueued none of the kind's programs are left out."""
    return [sum(m["dur"] for m in ms) / 1e6
            for ms in span_executions(events, kind, lo, hi) if ms]


def _modules(events: dict, lo: float, hi: float) -> list:
    """Program executions starting in [lo, hi], in start order."""
    return sorted((m for m in events["modules"] if lo <= m["start"] <= hi),
                  key=lambda m: m["start"])


def _spans(events: dict, kind: str, lo: float, hi: float) -> list:
    return sorted((s for s in events["spans"]
                   if s["name"] == kind and lo <= s["start"] <= hi),
                  key=lambda s: s["start"])


def _greedy(spans: list, mods: list, allowed=None) -> list:
    """Match each span, in order, to the first unmatched execution that
    starts after the span does: a device runs programs in the order they
    were launched, so the k-th launch of a kind is its k-th execution."""
    starts = [m["start"] for m in mods]
    out, used = [], set()
    for s in spans:
        j = bisect.bisect_left(starts, s["start"])
        while j < len(mods) and (j in used or (
                allowed is not None and mods[j]["name"] not in allowed)):
            j += 1
        if j < len(mods):
            used.add(j)
            out.append((s, mods[j]))
    return out


def launches(events: dict, kinds: tuple, lo: float, hi: float) -> dict:
    """kind -> [(span, execution)] for spans that each launch one program
    asynchronously (the engine's step): spans are matched to executions
    in order, first against every program, then only against the programs
    that most of a kind's matches landed on. Needs the aligned clock."""
    mods = _modules(events, lo, hi + 60e9)
    total: dict = defaultdict(int)
    for m in mods:
        total[m["name"]] += 1
    spans = {k: _spans(events, k, lo, hi) for k in kinds}
    first = {k: _greedy(spans[k], mods) for k in kinds}
    hits: dict = defaultdict(int)
    for pairs in first.values():
        for _, m in pairs:
            hits[m["name"]] += 1
    launched = {n for n, c in hits.items() if c >= 0.5 * total[n]}
    out = {}
    for k in kinds:
        own = {m["name"] for _, m in first[k]} & launched
        out[k] = _greedy(spans[k], mods, own)
    return out


STEP_KINDS = ("cb.prefill", "cb.decode")


def launch_device_ms(events: dict, kind: str, lo: float, hi: float,
                     kinds: tuple = STEP_KINDS) -> list:
    """Device ms of the program each `kind` span launched (matched among
    the launches of all `kinds`)."""
    return [m["dur"] / 1e6 for _, m in launches(events, kinds, lo, hi)[kind]]


def short_name(op: str) -> str:
    """`%fusion.3 = bf16[...] fusion(...)` -> `%fusion.3`."""
    return op.split(" = ", 1)[0]


def leaf_ops(events: dict, lo: float, hi: float) -> list:
    """Ops starting in [lo, hi] that contain no other op: a loop's op
    spans its body's ops, which would count twice."""
    ops = sorted((e for e in events["device"] if lo <= e["start"] <= hi),
                 key=lambda e: (e["device"], e["start"], -e["dur"]))
    out = []
    for a, b in zip(ops, ops[1:] + [None]):
        end = a["start"] + a["dur"]
        if b is None or b["device"] != a["device"] or b["start"] >= end \
                or b["start"] + b["dur"] > end:
            out.append(a)
    return out


def device_ns_by_name(events: dict, lo: float, hi: float,
                      substr: str = "") -> dict:
    """Summed leaf-op time per short op name, for ops whose full text
    contains `substr`."""
    out: dict = defaultdict(float)
    for e in leaf_ops(events, lo, hi):
        if substr in e["name"]:
            out[short_name(e["name"])] += e["dur"]
    return dict(out)


def launch_gaps_ms(events: dict, kinds: tuple, lo: float, hi: float) -> list:
    """Gaps between consecutive executions of the programs launched in
    spans of `kinds`: one execution's end to the next one's start."""
    ex = sorted((m["start"], m["start"] + m["dur"])
                for pairs in launches(events, kinds, lo, hi).values()
                for _, m in pairs)
    return [(b0 - a1) / 1e6 for (a0, a1), (b0, b1) in zip(ex, ex[1:])]
