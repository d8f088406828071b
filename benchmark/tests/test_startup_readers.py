"""The two readers of the program's start-up record (PR 36), on synthetic
snapshots and in a CPU rehearsal of one serving and one training cell.
Host seconds and counts are no device's numbers: a rehearsal may report
them."""

import importlib
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.readers import startup_count, startup_covered_s  # noqa: E402
from benchmark.tests.test_rehearsal import run_cell  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
SETUP = [m for m in BENCH["per_layer"] if m["name"].startswith("setup_")]
QUANTITIES = ("setup_trace_lower_s", "setup_backend_compile_s",
              "setup_first_run_s", "setup_executables",
              "setup_cache_misses", "setup_program_s")

T_OPEN = 100.0


def event(name, start, end, **attrs):
    return {"id": 0, "name": name, "start": start, "end": end,
            "parent": None, "attrs": attrs}


SNAPSHOT = [
    event("warmup", 10.0, 30.0),
    event("compile", 10.0, 20.0, module="jit_decode_paged"),
    # An outer trace with an inner one inside it, and one that overlaps.
    event("jit_trace", 10.0, 14.0, fun="decode_paged"),
    event("jit_trace", 11.0, 12.0, fun="add"),
    event("jit_trace", 13.0, 15.0, fun="other"),
    event("jit_lower", 15.0, 16.0, fun="decode_paged"),
    event("backend_compile", 16.0, 19.0, fun="decode_paged", cache_hit=True),
    event("first_run", 19.0, 20.0, fun="decode_paged"),
    event("backend_compile", 40.0, 42.5, fun="_make", cache_hit=False),
    event("backend_compile", 50.0, 50.5, fun="add", cache_hit=None),
    event("ready", 60.0, 60.0, scope="engine"),
    # After the window's opening: not set-up.
    event("backend_compile", 99.5, 100.5, fun="straddles", cache_hit=False),
    event("jit_trace", 101.0, 109.0, fun="reference"),
    event("backend_compile", 110.0, 120.0, fun="reference", cache_hit=False),
]


@pytest.fixture
def ctx(monkeypatch):
    """A reader's context over ``SNAPSHOT``, in the program's place."""
    said = []
    module = types.ModuleType("mpit_tpu.obs.startup")
    module.snapshot = lambda: {"events": list(module.events)}
    module.events = SNAPSHOT
    monkeypatch.setitem(sys.modules, "mpit_tpu.obs.startup", module)
    import mpit_tpu.obs

    monkeypatch.setattr(mpit_tpu.obs, "startup", module, raising=False)
    return {"run": {"t_open": T_OPEN}, "module": module, "said": said,
            "say": lambda kind, **fields: said.append((kind, fields))}


@pytest.mark.parametrize("names,want", [
    (["jit_trace", "jit_lower"], 6.0),  # 10-15 once, and 15-16
    (["backend_compile"], 6.0),  # 3 + 2.5 + 0.5; none past the opening
    (["first_run"], 1.0),
    (None, 23.0),  # warmup holds its children: 20, and 2.5 + 0.5
    (["no_such_span"], 0.0),
], ids=["trace_lower", "backend_compile", "first_run", "program", "none"])
def test_covered_seconds_are_a_union_up_to_the_opening(ctx, names, want):
    assert startup_covered_s.read(ctx, names=names) == pytest.approx(want)


@pytest.mark.parametrize("where,want", [
    (None, 3.0), ({"cache_hit": False}, 1.0), ({"cache_hit": True}, 1.0),
    ({"cache_hit": None}, 1.0), ({"fun": "nothing"}, 0.0),
], ids=["all", "misses", "hits", "uncached", "no-match"])
def test_counts_events_by_name_and_attributes(ctx, where, want):
    assert startup_count.read(ctx, "backend_compile", where=where) == want
    assert not ctx["said"]


def test_the_count_says_the_longest_events_by_name(ctx):
    assert startup_count.read(ctx, "backend_compile", say=3) == 3.0
    ((kind, fields),) = ctx["said"]
    assert kind == "startup" and fields["events"] == 11
    assert fields["longest"] == [
        ["warmup", None, 20.0, None],
        ["compile", "jit_decode_paged", 10.0, None],
        ["jit_trace", "decode_paged", 4.0, None]]


def test_none_with_an_empty_record(ctx):
    ctx["module"].events = [e for e in SNAPSHOT if e["end"] > T_OPEN]
    assert startup_covered_s.read(ctx, names=None) is None
    assert startup_count.read(ctx, "backend_compile", say=10) is None
    assert not ctx["said"]


def test_none_where_the_program_has_no_such_module(ctx, monkeypatch):
    """The parent of PR 36: the import fails, the line leaves them out."""
    import mpit_tpu.obs

    monkeypatch.setitem(sys.modules, "mpit_tpu.obs.startup", None)
    monkeypatch.delattr(mpit_tpu.obs, "startup")
    assert startup_covered_s.read(ctx, names=None) is None
    assert startup_count.read(ctx, "backend_compile") is None


@pytest.mark.parametrize("metric", SETUP, ids=[m["name"] for m in SETUP])
def test_every_entry_has_its_file_its_cells_and_moves_setup(metric):
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           metric["name"] + ".json")) as f:
        spec = json.load(f)
    cells = {c["name"] for c in BENCH["workloads"]}
    assert metric["moves"] == spec["moves"] == "setup_s"
    assert metric["better"] == "lower"
    assert metric["workloads"] and set(metric["workloads"]) <= cells
    for key in ("unit", "layer", "source"):
        assert metric[key] == spec[key]
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    assert callable(reader.read)
    assert metric["unit"] == ("s" if spec["reader"] == "startup_covered_s"
                              else "count")


def test_every_cell_reports_each_quantity_once():
    for cell in BENCH["workloads"]:
        got = sorted(m["name"].split(".")[0] for m in SETUP
                     if cell["name"] in m["workloads"])
        assert got == sorted(QUANTITIES), cell["name"]


@pytest.mark.parametrize("cell", ["gpt2l-serve-offline-decode",
                                  "gpt2s-train-1chip"])
def test_a_rehearsal_returns_the_six(cell):
    done = run_cell(cell, "--trace", "1")
    assert done.returncode == 3, done.stderr[-2000:]
    lines = [json.loads(l) for l in done.stdout.splitlines()
             if l.startswith("{")]
    got = {k.split(".")[0]: v["value"]
           for k, v in lines[-1]["metrics"].items() if k.startswith("setup_")}
    assert sorted(got) == sorted(QUANTITIES)
    setup_s = next(l for l in lines if l.get("note") == "setup")["setup_s"]
    for part in ("setup_trace_lower_s", "setup_backend_compile_s",
                 "setup_first_run_s"):
        assert 0 < got[part] <= got["setup_program_s"]
    assert got["setup_program_s"] <= setup_s
    assert got["setup_executables"] >= 3
    assert got["setup_cache_misses"] <= got["setup_executables"]
    (said,) = [l for l in lines if l.get("note") == "startup"]
    assert len(said["longest"]) == 10 and said["events"] >= 10
    names = {row[1] for row in said["longest"]}
    assert names & {"jit_decode_paged", "jit_prefill_paged", "jit_train_step"}
