"""Attribute a traced run's idle device time and time to first token to
what the program was doing, from the program's own spans and stamps.

    python3 chip_bench/attribute.py --workload <cell> --seed <n> \
        --seconds <s> [--out <dir>]

The program writes host spans at its layer boundaries into the
profiler's trace (`jax.profiler.TraceAnnotation`, the mechanism of the
harness's `cb.*` spans, so both sit on one clock): `engine.*` in the
serving engine (`step` with args `prefill_chunks` and `decode_rows`,
`admit`, `prefill` with `req` and `pos`, `decode`, `sample`, `emit`,
`idle`), `sched.flush` (arg `rows`) in the retrieval scheduler, and
`rag.embed`, `rag.search`, `rag.prompt` in the pipeline. It stamps each
`GenerationTicket` with `request_id` and `queue_s` (submit to first
admission). `run.run_cell` reads neither; this tool runs one traced cell
through `run.run_cell`, keeps the program spans as the trace is loaded
and each request's stamps as `_settle` copies its records, and reads:

  idle_by_label     the window's idle device time by label: the harness's
                    `cb.*` label (`trace.idle_gaps`) where it has one,
                    else the innermost program span open at the gap's
                    midpoint on the engine thread (the thread whose spans
                    include `engine.step`), else on any thread, else
                    `no span`
  engine_queue_p90_ms   p90 of `queue_s` over the window's requests
  prefill_span_p90_ms   p90 of `first_token_s - queue_s`
  engine_host_ms    median self time of the window's `engine.step` spans:
                    duration minus their `engine.sample` children
  embed_ms.rag      mean `rag.embed` duration in the window
  ttft_parts_ms     p50 and p90 of each part of a request's time to first
                    token: client lag, retrieval wait, engine queue,
                    admission to first token, and the rest (prompt
                    assembly, submission, delivery to the client)
  prefill_chunks    `engine.step`'s `prefill_chunks` over the window's
                    steps, and over the steps at least as long as the
                    traced run's `itl_p95_ms`

The run's own result object is the last line but one of standard output
and this tool's readings the last; with `--out`, the program spans and
the labelled gaps are written there as JSON too.
"""
from __future__ import annotations

import argparse
import bisect
import contextlib
import json
import os
import sys
import time
from collections import Counter, defaultdict

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chip_bench import spec, trace  # noqa: E402

PROGRAM_PREFIXES = ("engine.", "sched.", "rag.")
ENGINE_SPAN = "engine.step"
NO_SPAN = "no span"


def _plain(v):
    return v if isinstance(v, (int, float, str)) else str(v)


def load_program_spans(path: str) -> list:
    """The program's host spans in an `.xplane.pb`: name, start, dur (ns,
    host clock), thread, args. A plane's lines are told apart by index,
    since the profiler names the line of every Python thread alike."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(trace.DEVICE_PLANE_PREFIX):
            continue
        for i, line in enumerate(plane.lines):
            thread = f"{plane.name}/{line.name}/{i}"
            for e in line.events:
                if e.name.startswith(PROGRAM_PREFIXES):
                    out.append({
                        "name": e.name, "start": float(e.start_ns),
                        "dur": float(e.duration_ns), "thread": thread,
                        "args": {k: _plain(v)
                                 for k, v in trace._stats(e).items()}})
    return out


def _gap_midpoints(events: dict, device: str, lo: float, hi: float) -> list:
    """The midpoints of `trace.idle_gaps`'s gaps, in its order."""
    iv = trace.merged_intervals(events, device, lo, hi)
    edges = [lo] + [x for a, b in iv for x in (a, b)] + [hi]
    return [(a + b) / 2 for a, b in zip(edges[::2], edges[1::2]) if b > a]


def label_idle_gaps(events: dict, spans: list, device: str, lo: float,
                    hi: float) -> list:
    """`trace.idle_gaps`, with each gap it leaves as `no span` labelled by
    the innermost (latest-starting) program span open at the gap's
    midpoint on an engine thread, else on any thread."""
    gaps = trace.idle_gaps(events, device, lo, hi)
    engine = {s["thread"] for s in spans if s["name"] == ENGINE_SPAN}
    ordered = sorted(spans, key=lambda s: (s["start"], -s["dur"]))
    out, active, i = [], [], 0
    for (label, gap), mid in zip(gaps, _gap_midpoints(events, device, lo,
                                                      hi)):
        if label != NO_SPAN:
            out.append((label, gap))
            continue
        while i < len(ordered) and ordered[i]["start"] <= mid:
            active.append(ordered[i])
            i += 1
        active = [s for s in active if s["start"] + s["dur"] >= mid]
        open_ = [s for s in active if s["thread"] in engine] or active
        out.append((open_[-1]["name"] if open_ else NO_SPAN, gap))
    return out


def idle_by_label(events: dict, spans: list, device: str, lo: float,
                  hi: float) -> list:
    """[(label, idle ns, gaps)], the most idle time first."""
    tot: dict = defaultdict(float)
    n: Counter = Counter()
    for label, gap in label_idle_gaps(events, spans, device, lo, hi):
        tot[label] += gap
        n[label] += 1
    return sorted(((k, v, n[k]) for k, v in tot.items()),
                  key=lambda x: -x[1])


def in_window(spans: list, name: str, lo: float, hi: float) -> list:
    return [s for s in spans if s["name"] == name and lo <= s["start"] <= hi]


def self_ms(spans: list, parent: str, child: str, lo: float,
            hi: float) -> list:
    """For each `parent` span starting in [lo, hi]: its duration minus the
    `child` spans nested in it on its own thread (ms)."""
    kids: dict = defaultdict(list)
    for s in spans:
        if s["name"] == child:
            kids[s["thread"]].append((s["start"], s["dur"]))
    for v in kids.values():
        v.sort()
    out = []
    for p in in_window(spans, parent, lo, hi):
        ks, end = kids[p["thread"]], p["start"] + p["dur"]
        j, inside = bisect.bisect_left(ks, (p["start"], -1.0)), 0.0
        while j < len(ks) and ks[j][0] <= end:
            if ks[j][0] + ks[j][1] <= end:
                inside += ks[j][1]
            j += 1
        out.append((p["dur"] - inside) / 1e6)
    return out


def _pct(values: list, q: float):
    import numpy as np

    return float(np.percentile(values, q)) if values else None


def request_stamps(req) -> dict:
    """One request's host stamps (ms) and its ticket's stamps, read as
    `_settle` copies its records (before it drops the tickets)."""
    rt, gen = req.retrieval, req.gen
    ms = {"ttft": 1e3 * (req.stamps[0] - req.due) if req.stamps else None,
          "lag": 1e3 * (req.submitted - req.due),
          "retrieval_wait": (1e3 * rt.wait_s if rt is not None
                             and rt.wait_s is not None else None)}
    queue = getattr(gen, "queue_s", None)
    first = getattr(gen, "first_token_s", None)
    ms["queue"] = 1e3 * queue if queue is not None else None
    ms["first_token"] = 1e3 * first if first is not None else None
    ms["request_id"] = getattr(gen, "request_id", None)
    return ms


def ttft_parts(stamps: list) -> dict:
    """p50 and p90 (ms) of each part of the time to first token, over the
    requests that have every stamp."""
    rows = []
    for s in stamps:
        if None in (s["ttft"], s["retrieval_wait"], s["queue"],
                    s["first_token"]):
            continue
        prefill = s["first_token"] - s["queue"]
        rows.append({"ttft": s["ttft"], "lag": s["lag"],
                     "retrieval_wait": s["retrieval_wait"],
                     "queue": s["queue"], "prefill": prefill,
                     "rest": s["ttft"] - s["lag"] - s["retrieval_wait"]
                     - s["first_token"]})
    return {"n": len(rows),
            **{k: {"p50": _pct([r[k] for r in rows], 50),
                   "p90": _pct([r[k] for r in rows], 90)}
               for k in ("ttft", "lag", "retrieval_wait", "queue",
                         "prefill", "rest")}}


def attribute(events: dict, spans: list, stamps: list,
              itl_p95_ms=None) -> dict:
    """This tool's readings (module docstring) of one traced window."""
    lo, hi = trace.window_of(events)
    device = (trace.devices(events) or ["none"])[0]
    idle = idle_by_label(events, spans, device, lo, hi)
    idle_ns = sum(v for _, v, _ in idle)
    steps = in_window(spans, ENGINE_SPAN, lo, hi)
    engine = {s["thread"] for s in steps}
    on_engine = [s for s in spans if s["thread"] in engine
                 and lo <= s["start"] <= hi and s["name"] != "engine.idle"]
    host = self_ms(spans, ENGINE_SPAN, "engine.sample", lo, hi)
    embed = [s["dur"] / 1e6 for s in in_window(spans, "rag.embed", lo, hi)]
    queue = [s["queue"] for s in stamps if s["queue"] is not None]
    prefill = [s["first_token"] - s["queue"] for s in stamps
               if s["queue"] is not None and s["first_token"] is not None]
    slow = [s for s in steps
            if itl_p95_ms is not None and s["dur"] / 1e6 >= itl_p95_ms]

    def chunks(ss):
        return dict(sorted(Counter(int(s["args"].get("prefill_chunks", -1))
                                   for s in ss).items()))

    by_name: dict = defaultdict(list)
    for s in spans:
        if lo <= s["start"] <= hi:
            by_name[s["name"]].append(s["dur"] / 1e6)
    return {
        "idle_s": idle_ns / 1e9,
        "idle_by_label": [[k, v / 1e9, n] for k, v, n in idle],
        "no_span_share_of_idle": (sum(v for k, v, _ in idle if k == NO_SPAN)
                                  / idle_ns if idle_ns else None),
        "engine_queue_p90_ms": _pct(queue, 90),
        "prefill_span_p90_ms": _pct(prefill, 90),
        "engine_host_ms": _pct(host, 50),
        "embed_ms.rag": sum(embed) / len(embed) if embed else None,
        "ttft_parts_ms": ttft_parts(stamps),
        "prefill_chunks": {"all_steps": chunks(steps),
                           "steps_at_or_over_itl_p95": chunks(slow),
                           "itl_p95_ms": itl_p95_ms},
        "spans_per_step": len(on_engine) / len(steps) if steps else None,
        "span_ms": {k: {"n": len(v), "mean": sum(v) / len(v),
                        "p50": _pct(v, 50), "p95": _pct(v, 95),
                        "sum_s": sum(v) / 1e3}
                    for k, v in sorted(by_name.items())},
    }


@contextlib.contextmanager
def _observed(seen: dict):
    """While open, `run.run_cell` keeps what this tool reads in `seen`:
    the program spans as the trace is loaded, each request's stamps as
    `_settle` copies its records, and the traced window's end-to-end
    metrics."""
    load, load_module = trace.load_events, spec.driver
    seen["stamps"] = []

    def load_events(path):
        seen["spans"] = load_program_spans(path)
        seen["events"] = load(path)
        return seen["events"]

    def observed_module(root, name):
        drv = load_module(root, name)
        settle, context = drv._settle, drv.layer_context

        def _settle(req, eos):
            seen["stamps"].append(request_stamps(req))
            settle(req, eos)

        def layer_context(st, rec):
            seen["end_to_end"] = drv.end_to_end(st, rec)
            return context(st, rec)

        drv._settle, drv.layer_context = _settle, layer_context
        return drv

    trace.load_events, spec.driver = load_events, observed_module
    try:
        yield
    finally:
        trace.load_events, spec.driver = load, load_module


def run(cell, seed: int, seconds: float, require_tpu: bool = True,
        out_dir: str | None = None) -> tuple:
    """(run_cell's traced result, this tool's readings) of one cell."""
    from chip_bench import run as bench

    seen: dict = {}
    with _observed(seen):
        result = bench.run_cell(cell, seed, seconds, True,
                                require_tpu=require_tpu,
                                start=time.perf_counter())
    e2e = seen.get("end_to_end", {})
    readings = attribute(seen["events"], seen["spans"], seen["stamps"],
                         e2e.get("itl_p95_ms"))
    readings["end_to_end_traced"] = e2e
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        lo, hi = trace.window_of(seen["events"])
        device = (trace.devices(seen["events"]) or ["none"])[0]
        with open(os.path.join(out_dir, "program_spans.json"), "w") as f:
            json.dump({"window": [lo, hi], "spans": seen["spans"],
                       "stamps": seen["stamps"],
                       "gaps": label_idle_gaps(seen["events"],
                                               seen["spans"], device, lo,
                                               hi)}, f)
    return result, readings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload, ROOT)
    result, readings = run(cell, args.seed, args.seconds,
                           out_dir=args.out)
    print(json.dumps(result), flush=True)
    print(json.dumps(readings), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
