"""The trace reduction of chip_bench/trace.py: busy union, idle gaps by
host span, device time per span and per op name, launch gaps."""
import json
import os

import pytest

from chip_bench import trace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "tpu_trace_small.json")


def ev(device=(), spans=(), launches=(), modules=()):
    return {
        "device": [dict(name=n, start=s, dur=d, device="/device:TPU:0",
                        module="m", program_id=1, run_id=r)
                   for n, s, d, r in device],
        "spans": [dict(name=n, start=s, dur=d, thread="t") for n, s, d in
                  spans],
        "launches": [dict(name="DoEnqueueProgram", start=s, dur=1.0,
                          thread="t", run_id=r) for s, r in launches],
        "modules": [dict(name=(m + ("m",))[3], start=m[0], dur=m[1],
                         device="/device:TPU:0", run_id=m[2])
                    for m in modules],
    }


SMALL = ev(  # run ids of ops: the program execution each belongs to
    device=[("fusion", 10, 20, 1), ("dot", 25, 10, 1),    # 10..35
            ("fusion", 50, 10, 2), ("kernel_paged_attend", 80, 15, 3)],
    spans=[("cb.window", 0, 100), ("cb.prefill", 5, 3), ("cb.decode", 40, 2),
           ("cb.decode", 70, 2), ("cb.sample", 36, 12)],
    launches=[(6, 1), (41, 2), (71, 3)],     # each enqueued in its span
    modules=[(10, 25, 1, "prefill"), (50, 10, 2, "decode"),
             (80, 15, 3, "decode")])


def test_busy_union_merges_overlaps_and_clips():
    assert trace.merged_intervals(SMALL, "/device:TPU:0", 0, 100) == [
        [10, 35], [50, 60], [80, 95]]
    assert trace.busy_ns(SMALL, "/device:TPU:0", 0, 100) == 25 + 10 + 15
    assert trace.busy_ns(SMALL, "/device:TPU:0", 20, 55) == 15 + 5


def test_idle_gaps_are_labelled_by_the_open_span():
    gaps = trace.idle_gaps(SMALL, "/device:TPU:0", 0, 100)
    assert [g for _, g in gaps] == [10, 15, 20, 5]
    # 0..10 mid 5: cb.prefill (5..8) is open; 35..50 mid 42.5: cb.sample
    # (36..48) is the latest-starting open span; 60..80 mid 70: cb.decode
    assert [n for n, _ in gaps] == ["cb.prefill", "cb.sample", "cb.decode",
                                    "no span"]
    assert trace.idle_by_span(SMALL, "/device:TPU:0", 0, 100)[0] == (
        "cb.decode", 20)


def test_device_time_per_launch_and_name():
    # spans are matched in order to the executions that start after them
    assert trace.launch_device_ms(SMALL, "cb.decode", 0, 100) == [1e-5,
                                                                 1.5e-5]
    assert trace.launch_device_ms(SMALL, "cb.prefill", 0, 100) == [2.5e-5]
    assert trace.launch_device_ms(SMALL, "cb.decode", 60, 100) == [1.5e-5]
    # a search span owns what it enqueued (by run id), unless the program
    # is mostly enqueued outside such spans (another thread's step)
    e = ev(spans=[("cb.search", 0, 20), ("cb.search", 100, 30)],
           launches=[(2, 1), (12, 2), (45, 3), (65, 4), (105, 5)],
           modules=[(5, 8, 1, "search"), (15, 3, 2, "step"),
                    (50, 3, 3, "step"), (70, 3, 4, "step"),
                    (130, 12, 5, "search")])
    assert trace.span_device_ms(e, "cb.search", 0, 200) == [8e-6, 1.2e-5]
    by_name = trace.device_ns_by_name(SMALL, 0, 100)
    assert by_name == {"fusion": 30, "dot": 10, "kernel_paged_attend": 15}
    assert trace.device_ns_by_name(SMALL, 0, 100, "paged_attend") == {
        "kernel_paged_attend": 15}


def test_loop_ops_are_not_counted_twice():
    nested = ev(device=[("%while.1 = (s32[]) while(...)", 0, 100, None),
                        ("%fusion.2 = bf16[8] fusion(...)", 10, 30, None),
                        ("%dot.3 = bf16[8] dot(...)", 50, 20, None)])
    assert trace.device_ns_by_name(nested, 0, 100) == {"%fusion.2": 30,
                                                       "%dot.3": 20}


def test_launches_skip_programs_queued_from_other_threads():
    # a search program that another thread queued runs right after the
    # third decode span starts; decode spans claim it once in its four
    # runs, so it is not theirs, and the third decode is matched to its
    # own program behind it
    e = ev(spans=[("cb.decode", 0, 1), ("cb.decode", 100, 1),
                  ("cb.decode", 200, 1)],
           modules=[(5, 10, 1, "decode"), (50, 5, 2, "search"),
                    (105, 10, 3, "decode"), (150, 5, 4, "search"),
                    (202, 30, 5, "search"), (240, 10, 6, "decode"),
                    (260, 5, 7, "search")])
    assert trace.launch_device_ms(e, "cb.decode", 0, 300) == [1e-5, 1e-5,
                                                             1e-5]
    assert trace.launch_gaps_ms(e, ("cb.decode",), 0, 300) == [9e-5,
                                                               1.25e-4]


def test_clocks_are_aligned_by_the_earliest_start_after_an_enqueue():
    e = ev(launches=[(100, 1), (110, 2), (300, 2)],
           modules=[(1050, 20, 1), (1090, 5, 2)],
           device=[("op", 1050, 20, 1), ("op", 1090, 5, 2)])
    trace.align(e)
    assert e["offset"] == 950
    assert [m["start"] for m in e["modules"]] == [100, 140]
    assert [d["start"] for d in e["device"]] == [100, 140]


def test_recorded_tpu_trace():
    """A trace recorded on a v5e (chip_bench/record_trace_fixture.py) and
    reduced by `trace.load_events` without the clock alignment: three
    rounds of two prefill launches and one decode launch, synced once
    per round."""
    with open(FIXTURE) as f:
        events = trace.align(json.load(f))
    assert events["offset"] != 0        # the device keeps its own clock
    lo, hi = trace.window_of(events)
    dev = trace.devices(events)
    assert dev and dev[0].startswith("/device:TPU")
    busy = trace.busy_ns(events, dev[0], lo, hi)
    assert 0 < busy < hi - lo
    enq = trace.enqueues(events)
    late = [m["start"] - enq[m["run_id"]] for m in events["modules"]]
    assert min(late) == 0               # no program starts before its enqueue
    pre = trace.launch_device_ms(events, "cb.prefill", lo, hi)
    dec = trace.launch_device_ms(events, "cb.decode", lo, hi)
    assert len(pre) == 6 and len(dec) == 3
    assert min(pre) > 10 * max(dec)     # the prefill program is the bigger
    gaps = trace.launch_gaps_ms(events, trace.STEP_KINDS, lo, hi)
    assert len(gaps) == 8 and min(gaps) >= 0
    gaps = trace.idle_gaps(events, dev[0], lo, hi)
    assert sum(g for _, g in gaps) == pytest.approx(hi - lo - busy)
