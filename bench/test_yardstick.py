"""Checks of the benchmark's own arithmetic: the trace reduction on a small
trace recorded on a TPU v5e and on a hand-made one, the CC tick's byte and
operation count, the peak table, and the plan arithmetic.

    PYTHONPATH=src python -m pytest bench
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent / "src"), str(HERE)]

import devtrace  # noqa: E402
import plan  # noqa: E402
import roofline  # noqa: E402


def hand_trace() -> devtrace.Trace:
    # window [100, 200); device ops overlap, one starts before the window
    ops = {"/device:TPU:0": [("fusion.1", 90, 20),        # clipped to [100,110)
                             ("_kernel_batched", 105, 10),  # [105,115)
                             ("fusion.2", 130, 10),       # [130,140)
                             ("_kernel_batched", 150, 20)]}  # [150,170)
    host = [("run_plan", 95, 110), ("block_until_ready", 120, 60),
            ("postprocess", 185, 15), (devtrace.WINDOW_SPAN, 100, 100)]
    return devtrace.Trace(ops=ops, host=host, window=(100, 200))


def test_busy_union_and_window():
    tr = hand_trace()
    # union of [100,115), [130,140), [150,170) = 15 + 10 + 20
    assert devtrace.busy_ns(tr) == 45
    assert devtrace.window_ns(tr) == 100


def test_kernel_ops_and_top_ops():
    tr = hand_trace()
    kern = devtrace.kernel_ops(tr, "_kernel")
    assert [e - s for _, s, e in kern] == [10, 20]
    top = devtrace.top_ops(tr)
    assert top[0] == ["_kernel_batched", pytest.approx(30e-9)]
    assert {name for name, _ in top} == {"_kernel_batched", "fusion.1",
                                         "fusion.2"}


def test_idle_gaps_by_host_span():
    tr = hand_trace()
    # gaps: [115,130) mid 122.5 -> block_until_ready (shorter than run_plan)
    #       [140,150) mid 145   -> block_until_ready
    #       [170,200) mid 185   -> postprocess
    gaps = dict(devtrace.idle_gaps(tr))
    assert gaps == {"block_until_ready": pytest.approx(25e-9),
                    "postprocess": pytest.approx(30e-9)}


def recorded_trace() -> devtrace.Trace:
    rec = json.loads((HERE / "testdata" / "v5e_trace_slice.json").read_text())
    return devtrace.Trace(ops={k: [tuple(e) for e in v]
                               for k, v in rec["ops"].items()},
                          host=[tuple(e) for e in rec["host"]],
                          window=tuple(rec["window"]))


def test_cc_tick_calls_are_one_per_scan_step():
    tr = devtrace.Trace(
        ops={"/device:TPU:0": [(f"%closed_call.1 {devtrace.KERNEL_MATCH}",
                                110 + 20 * i, 5) for i in range(4)]},
        host=[], window=(100, 200))
    assert len(devtrace.cc_tick_ops(tr, 4)) == 4
    # a second Mosaic kernel in the tick: the CC tick's calls are not told
    # apart, so nothing is read
    assert devtrace.cc_tick_ops(tr, 2) is None
    assert devtrace.cc_tick_ops(hand_trace(), 2) is None


def test_recorded_v5e_trace():
    rec = json.loads((HERE / "testdata" / "v5e_trace_slice.json").read_text())
    tr = recorded_trace()
    want = rec["expected"]
    assert devtrace.busy_ns(tr) == want["busy_ns"]
    kern = devtrace.kernel_ops(tr, devtrace.KERNEL_MATCH)
    assert len(kern) == want["kernel_calls"] == 2
    assert sum(e - s for _, s, e in kern) == want["kernel_ns"]
    assert devtrace.top_ops(tr, 1)[0][0] == want["top_op"]


def test_cc_tick_count_by_hand_for_56_flows():
    config = plan.load_json("configs", "dumbbell_reno")
    assert roofline.live_flows(config) == 7 * 8
    # per flow: 12 CC-state words and 6 iteration-tracker words in and out,
    # 4 feedback words, the bytes per iteration and the rate: 42 words
    assert roofline.WORDS_PER_FLOW == 42
    assert roofline.cc_tick_bytes(config, 4) == 42 * 4 * 56 * 4 == 37632
    least, bound = roofline.least_seconds(config, 4, "TPU v5 lite")
    assert bound == "bytes"
    assert least == pytest.approx(37632 / 819e9)


def test_cc_tick_count_refuses_an_uncounted_transport():
    config = plan.load_json("configs", "dumbbell_reno")
    config["transport"]["algo"] = "cubic"
    with pytest.raises(ValueError):
        roofline.cc_tick_bytes(config, 4)
    with pytest.raises(ValueError):
        roofline.least_seconds(config, 4, "TPU v5 lite")


def test_unknown_device_is_an_error():
    with pytest.raises(ValueError):
        roofline.peaks("cpu")


def test_plan_arithmetic():
    dumbbell = plan.load_json("configs", "dumbbell_reno")
    assert plan.jobs_per_link(dumbbell) == 7
    # RoBERTa, the slowest: (0.26 s + 7 x 1.42 GB / 6.25 GB/s) x 4 iterations
    # x 1.6 x 0.25 = 2.96064 s of 20 us ticks
    assert plan.n_ticks(dumbbell) == 148032
    # the two-tier placement uses every ordered leaf pair, none to itself
    two_tier = dict(dumbbell, jobs=dict(dumbbell["jobs"], n=12),
                    fabric={"kind": "two_tier", "leaves": 4})
    assert sorted(plan.leaf_pairs(two_tier)) == sorted(
        (a, b) for a in range(4) for b in range(4) if a != b)
    # seeds and programs are a pure function of the run's seed, and the
    # points of one call get distinct program orders
    iso = plan.single_iso(dumbbell)
    a = plan.draw_calls(2**33 + 7, 3, 4, iso)
    assert a == plan.draw_calls(2**33 + 7, 3, 4, iso)
    # any two points of a call differ at some job by a program >= 30%
    # slower or faster in isolation
    for call in a:
        for x in call:
            for y in call:
                if x is not y:
                    gap = max(abs(iso[i] - iso[j]) / min(iso[i], iso[j])
                              for i, j in zip(x.order, y.order))
                    assert gap >= plan.DISTINCT
    names = plan.shuffled_programs(dumbbell, a[0][0].order)
    assert sorted(names) == sorted(dumbbell["jobs"]["cycle"])
    assert names[6] == "gpt3_hybrid"
