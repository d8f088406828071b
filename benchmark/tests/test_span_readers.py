"""The readers of the program's span tree, on hand-made spans."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark.readers import span_rid_gap_ms, span_self_ms  # noqa: E402

MS = 1e-3


def ctx_of(spans):
    return {"run": {"host_spans": [(n, a * MS, b * MS, attrs)
                                   for n, a, b, attrs in spans]}}


def test_self_time_counts_overlapping_children_once():
    spans = [
        ("tick", 0, 100, {}),
        ("admit", 1, 3, {}),            # not a named child: stays in self
        ("prefill", 10, 40, {}),
        ("decode", 30, 90, {}),         # overlaps prefill by 10
        ("decode_fetch", 50, 90, {}),   # a grandchild, not named
        ("tick", 105, 155, {}),
        ("decode", 102, 135, {}),       # starts before its tick: clipped
        ("tick", 155, 165, {}),         # no child at all
        ("decode", 400, 500, {}),       # outside every tick
    ]
    read = lambda: span_self_ms.read(ctx_of(spans), "tick", ["prefill", "decode"])
    # 100 - (10..90) = 20; 50 - (105..135) = 20; 10.
    assert read() == pytest.approx(20.0)
    spans.append(("tick", 165, 168, {}))
    assert read() == pytest.approx((10 + 20) / 2)  # the median of 20, 20, 10, 3
    assert span_self_ms.read(ctx_of(spans), "step", ["decode"]) is None
    # Where the ticks' time went, as means per tick (they add up).
    said = []
    ctx = dict(ctx_of(spans), say=lambda kind, **f: said.append((kind, f)))
    span_self_ms.read(ctx, "tick", ["prefill", "decode"])
    (kind, budget), = said
    assert (kind, budget["instances"]) == ("span_budget", 4)
    assert budget["mean_ms"] == pytest.approx((100 + 50 + 10 + 3) / 4)
    assert budget["inside_mean_ms"] == pytest.approx({
        "admit": 2 / 4, "prefill": 30 / 4, "decode": 60 / 4,
        "decode_fetch": 40 / 4})  # the decode that starts early is in no tick


def test_an_empty_window_gives_none():
    assert span_self_ms.read(ctx_of([]), "tick", ["decode"]) is None
    assert span_rid_gap_ms.read(ctx_of([]), "decode", 90, 1) is None


def test_gaps_between_the_ticks_that_list_a_request():
    ticks = [(0, 10, [1, 2]), (10, 20, [1, 2]), (30, 40, [1]),  # 2 sits one out
             (40, 50, [1, 2]), (50, 65, [2, 3])]
    spans = [("decode", a, b, {"rids": rids}) for a, b, rids in ticks]
    spans += [("prefill", 20, 30, {"rids": [3]}), ("decode", 70, 80, {})]
    # rid 1: 10, 20, 10; rid 2: 10, 30 (it skipped a tick), 15; rid 3: none.
    read = lambda q, least=1: span_rid_gap_ms.read(ctx_of(spans), "decode", q, least)
    assert read(0) == pytest.approx(10.0)
    assert read(50) == pytest.approx(15.0)  # 10 10 10 | 15 | 20 30
    assert read(90) == read(100) == pytest.approx(30.0)
    assert read(90, least=6) == pytest.approx(30.0)
    assert read(90, least=7) is None  # too few gaps to rest a tail on
