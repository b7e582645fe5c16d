"""chip_bench/attribute.py: idle gaps labelled by the program's own spans,
step self time, and the tool's readings of a tiny traced run on the CPU;
and the harness's nine per-layer readers replayed on the recorded v5e
trace, whose values a change to the trace reduction must keep."""
import json
import math
import os

import numpy as np
import pytest

import cb_tiny
from chip_bench import attribute, peaks, spec, trace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "tpu_trace_small.json")
DEV = "/device:TPU:0"
ENGINE, SCHED = "host/python/1", "host/python/2"


def ev(ops, spans=()):
    """Device ops [(start, dur)] and harness spans [(name, start, dur)]."""
    return {
        "device": [dict(name="op", start=s, dur=d, device=DEV, module="m",
                        program_id=1, run_id=None) for s, d in ops],
        "spans": [dict(name=n, start=s, dur=d, thread="t")
                  for n, s, d in spans],
        "launches": [], "modules": []}


def ps(name, start, dur, thread=ENGINE, **args):
    return dict(name=name, start=float(start), dur=float(dur),
                thread=thread, args=args)


# gaps 0..10 (mid 5), 20..40 (mid 30), 50..70 (mid 60), 80..100 (mid 90)
OPS = [(10, 10), (40, 10), (70, 10)]


def test_harness_label_is_kept_and_no_span_takes_the_engine_span():
    events = ev(OPS, spans=[("cb.window", 0, 100), ("cb.prefill", 25, 10)])
    spans = [ps("engine.step", 0, 100),
             ps("engine.prefill", 22, 15, req=0, pos=0),
             ps("engine.emit", 55, 10),
             ps("rag.embed", 58, 5, SCHED)]     # starts later, other thread
    gaps = attribute.label_idle_gaps(events, spans, DEV, 0, 100)
    assert gaps == [("engine.step", 10), ("cb.prefill", 20),
                    ("engine.emit", 20), ("engine.step", 20)]


def test_without_the_engine_the_innermost_span_of_any_thread_labels():
    events = ev(OPS, spans=[("cb.window", 0, 100)])
    spans = [ps("sched.flush", 52, 15, SCHED, rows=4),
             ps("rag.embed", 55, 10, SCHED),
             ps("engine.step", 85, 2)]          # closed before the midpoint
    gaps = attribute.label_idle_gaps(events, spans, DEV, 0, 100)
    assert gaps == [("no span", 10), ("no span", 20), ("rag.embed", 20),
                    ("no span", 20)]
    assert attribute.idle_by_label(events, spans, DEV, 0, 100) == [
        ("no span", 50, 3), ("rag.embed", 20, 1)]


@pytest.mark.parametrize("source", ["hand-built", "recorded"])
def test_without_program_spans_the_gaps_are_the_harness_gaps(source):
    if source == "recorded":
        with open(FIXTURE) as f:
            events = trace.align(json.load(f))
    else:
        events = ev(OPS, spans=[("cb.window", 0, 100),
                                ("cb.decode", 45, 20)])
    lo, hi = trace.window_of(events)
    dev = trace.devices(events)[0]
    assert attribute.label_idle_gaps(events, [], dev, lo, hi) == \
        trace.idle_gaps(events, dev, lo, hi)


def test_step_self_time_leaves_out_its_own_samples_only():
    spans = [ps("engine.step", 0, 10e6), ps("engine.sample", 2e6, 3e6),
             ps("engine.sample", 6e6, 1e6),
             ps("engine.step", 20e6, 5e6),
             ps("engine.sample", 21e6, 2e6, SCHED),  # another thread's
             ps("engine.step", 200e6, 5e6)]          # outside the window
    assert attribute.self_ms(spans, "engine.step", "engine.sample",
                             0, 100e6) == [6.0, 5.0]


def _fixture_ctx(events):
    lo, hi = trace.window_of(events)
    return {"events": events, "window": (lo, hi),
            "device": trace.devices(events)[0],
            "peaks": peaks.PEAKS["TPU v5 lite"],
            "host": {"lag_ms": np.array([0.5, 1.0, 4.0]),
                     "retrieval_wait_ms": np.array([10.0, 20.0, 30.0, 40.0]),
                     "paged_attend_work": [(1e9, 1e6)],
                     "step_flops": 1e8}}


# the readers' values on the recorded trace (the host inputs are made up)
FIXTURE_READINGS = {
    "gen_lag_p99_ms": 3.94,
    "retrieval_wait_p90_ms": 37.0,
    "search_device_ms.rag": None,          # the trace has no cb.search
    "step_gap_ms": 0.24977899999999997,
    "decode_step_ms": 0.0034373333333333335,
    "prefill_chunk_ms": 0.047216666666666664,
    "paged_attend_roofline": None,         # nor a paged_attend kernel
    "step_mfu": 0.17288605819856461,
    "device_idle_share.gen": 90.53476642867739,
}


@pytest.mark.parametrize("name", sorted(FIXTURE_READINGS))
def test_readers_on_the_recorded_trace(name):
    with open(FIXTURE) as f:
        events = trace.align(json.load(f))
    value = spec.metric_reader(cb_tiny.REPO, name)(_fixture_ctx(events))
    want = FIXTURE_READINGS[name]
    assert value == (want if want is None else pytest.approx(want,
                                                             rel=1e-12))


# ------------------------------------------- a tiny traced run on the CPU
SEED = 2**33 + 29


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("attr")
    root = cb_tiny.make_root(str(tmp))
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp / "jc"))
        result, readings = attribute.run(
            spec.resolve("tiny-rag", root), SEED, 2.0, require_tpu=False,
            out_dir=str(tmp / "out"))
    with open(tmp / "out" / "program_spans.json") as f:
        dump = json.load(f)
    return result, readings, dump


def test_tiny_traced_run_has_every_program_span(tiny_run):
    result, readings, dump = tiny_run
    assert result["attempted"] == 6 and result["failed"] == 0
    names = {s["name"] for s in dump["spans"]}
    assert names >= {"engine.step", "engine.admit", "engine.prefill",
                     "engine.decode", "engine.sample", "engine.emit",
                     "engine.idle", "sched.flush", "rag.embed",
                     "rag.search", "rag.prompt"}
    ids = {s["request_id"] for s in dump["stamps"]}
    assert len(ids) == 6 and None not in ids
    assert readings["ttft_parts_ms"]["n"] == 6
    assert sum(readings["prefill_chunks"]["all_steps"].values()) == \
        readings["span_ms"]["engine.step"]["n"]


@pytest.mark.parametrize("name", ["engine_queue_p90_ms",
                                  "prefill_span_p90_ms", "engine_host_ms",
                                  "embed_ms.rag"])
def test_tiny_traced_run_reads(tiny_run, name):
    _, readings, _ = tiny_run
    value = readings[name]
    assert value is not None and math.isfinite(value) and value >= 0
