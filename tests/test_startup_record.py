"""The start-up record (ISSUE 36): ``mpit_tpu.obs.startup``.

What a process does before its first tick or step is a bounded, always-on
list of spans named by executable, fed by JAX's own compile events and by
spans at the program's start-up boundaries. Pinned here, on the CPU:

- the listener: synthetic ``jax.monitoring`` events become ``jit_trace``
  / ``jit_lower`` / ``backend_compile`` with ``fun``, the recorder's
  clock and the thread's open span as parent; nested traces keep the
  outermost; the cache's events land on the backend compile of their
  thread;
- the record: always on, mirrored into an enabled recorder, bounded,
  listeners registered once;
- ``ready`` and what follows it: ``compile_after_ready`` names the
  function, ``unexpected_recompile`` too;
- the structure a tiny engine's warm-up and a train step's first call
  leave (no assertion on seconds).
"""

from __future__ import annotations

import inspect
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpit_tpu
from mpit_tpu import obs
from mpit_tpu.models import GPT2, GPT2Config
from mpit_tpu.obs import roofline, startup
from mpit_tpu.serve import Engine, Request, Server, warm_engine
from mpit_tpu.serve import engine as engine_module

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
CACHE = "/jax/compilation_cache/"

CFG = GPT2Config.tiny(
    vocab_size=64, max_seq_len=64, num_layers=2, num_heads=2, d_model=32,
    dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def params():
    return jax.jit(GPT2(CFG).init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]


@pytest.fixture(autouse=True)
def fresh_record():
    startup.install()
    startup.reset()
    yield
    startup.reset()
    obs.disable()


def _events(name=None):
    events = startup.snapshot()["events"]
    return [e for e in events if name is None or e["name"] == name]


def _one(name):
    (event,) = _events(name)
    return event


def _compile_of(fun):
    """Feed the three events of one executable, as JAX orders them."""
    startup._on_duration(TRACE, 0.003, fun_name=fun)
    startup._on_duration(LOWER, 0.002, fun_name=f"jit({fun})")
    startup._on_duration(BACKEND, 0.001, fun_name=f"jit({fun})")


def _children(parent):
    return [e for e in _events() if e["parent"] == parent["id"]]


class TestListener:
    @pytest.mark.parametrize("event,name,fun_name", [
        (TRACE, "jit_trace", "decode_paged"),
        (LOWER, "jit_lower", "jit(decode_paged)"),
        (BACKEND, "backend_compile", "jit(decode_paged)"),
    ])
    def test_events_become_spans_named_by_executable(
            self, event, name, fun_name):
        with startup.span("warmup") as warm:
            before = time.perf_counter()
            startup._on_duration(event, 0.25, fun_name=fun_name)
            after = time.perf_counter()
        got = _one(name)
        assert got["attrs"]["fun"] == "decode_paged"
        # The recorder's clock: the end is the receipt, the start the
        # end less the duration.
        assert before <= got["end"] <= after
        assert got["end"] - got["start"] == pytest.approx(0.25)
        assert got["parent"] == warm.id == _one("warmup")["id"]

    def test_only_the_duration_events_are_listened_to(self):
        """JAX also reports each as a time span on ``time.time()``: a
        listener there would count every event twice on another clock."""
        from jax._src import monitoring

        assert startup._on_duration in (
            monitoring.get_event_duration_listeners())
        assert startup._on_event in monitoring.get_event_listeners()
        assert startup._on_scalar in monitoring.get_scalar_listeners()
        assert not [
            f for f in monitoring.get_event_time_span_listeners()
            if getattr(f, "__module__", "") == startup.__name__
        ]

    def test_nested_traces_keep_the_outermost(self):
        """A jitted function that calls jitted functions traces them
        inside its own trace (JAX says when each begins), and an eager
        operation on a constant may even compile in there."""
        def begin(event):
            startup._on_scalar(event, time.time(), fun_name="any")

        begin(TRACE)  # decode_paged
        begin(TRACE)
        startup._on_duration(TRACE, 0.001, fun_name="add")
        for event, fun in ((TRACE, "arange"), (LOWER, "jit(arange)"),
                           (BACKEND, "jit(arange)")):
            begin(event)
            startup._on_duration(event, 0.002, fun_name=fun)
        begin(TRACE)
        startup._on_duration(TRACE, 0.002, fun_name="_where")
        startup._on_duration(TRACE, 0.5, fun_name="decode_paged")
        begin(LOWER)
        begin(TRACE)  # a lowering rule's
        startup._on_duration(TRACE, 0.001, fun_name="less")
        startup._on_duration(LOWER, 0.1, fun_name="jit(decode_paged)")
        begin(BACKEND)
        startup._on_duration(BACKEND, 0.001, fun_name="jit(decode_paged)")
        assert [(e["name"], e["attrs"]["fun"]) for e in _events()] == [
            ("backend_compile", "arange"),  # an executable always counts
            ("jit_trace", "decode_paged"), ("jit_lower", "decode_paged"),
            ("backend_compile", "decode_paged")]

    def test_late_eager_traces_that_build_nothing_are_not_kept(self):
        startup.ready("engine")
        for _ in range(50):  # cached executables: a lone trace each
            startup._on_duration(TRACE, 0.001, fun_name="add")
        assert [e["name"] for e in _events()] == ["ready"]
        _compile_of("late_shape")
        late = _one("compile_after_ready")["attrs"]
        assert late["jit_trace_s"] == 0.003 and late["jit_lower_s"] == 0.002
        assert [e["name"] for e in _events()] == [
            "ready", "jit_trace", "jit_lower", "backend_compile",
            "compile_after_ready"]

    def test_cache_events_land_on_their_threads_backend_compile(self):
        def other_thread():
            startup._on_event(CACHE + "cache_misses")
            startup._on_duration(BACKEND, 0.01, fun_name="jit(copy_page)")

        startup._on_event(CACHE + "cache_hits")
        startup._on_duration(CACHE + "compile_time_saved_sec", 7.5)
        startup._on_duration(CACHE + "cache_retrieval_time_sec", 0.5)
        worker = threading.Thread(target=other_thread)
        worker.start()
        worker.join()
        startup._on_duration(BACKEND, 0.6, fun_name="jit(decode_paged)")
        startup._on_duration(BACKEND, 0.1, fun_name="jit(add)")
        by_fun = {e["attrs"]["fun"]: e["attrs"]
                  for e in _events("backend_compile")}
        assert by_fun["decode_paged"] == {
            "fun": "decode_paged", "cache_hit": True, "cache_read_s": 0.5,
            "saved_s": 7.5}
        assert by_fun["copy_page"] == {"fun": "copy_page",
                                       "cache_hit": False}
        # Neither event: the cache was not asked or keeps no such entry.
        assert by_fun["add"] == {"fun": "add", "cache_hit": None}

    def test_install_twice_registers_once(self):
        from jax._src import monitoring

        startup.install()
        startup.install()
        assert monitoring.get_event_duration_listeners().count(
            startup._on_duration) == 1
        assert monitoring.get_event_listeners().count(
            startup._on_event) == 1
        assert monitoring.get_scalar_listeners().count(
            startup._on_scalar) == 1


class TestRecord:
    def test_fills_with_no_recorder_and_mirrors_into_one(self):
        assert not obs.enabled()
        with startup.span("engine_build"):
            _compile_of("decode_paged")
        assert [e["name"] for e in _events()] == [
            "jit_trace", "jit_lower", "backend_compile", "engine_build"]
        rec = obs.enable(obs.Recorder())
        with startup.span("warmup"):
            _compile_of("prefill_paged")
        phases = rec.summary()["phases"]
        assert {n: p["count"] for n, p in phases.items()} == {
            "jit_trace": 1, "jit_lower": 1, "backend_compile": 1,
            "warmup": 1}
        assert phases["backend_compile"]["labels"] == {
            "fun": ["prefill_paged"]}
        assert len(_events()) == 8

    def test_bounded_and_counts_what_it_drops(self, monkeypatch):
        monkeypatch.setattr(startup, "MAX_EVENTS", 5)
        for _ in range(8):
            startup._on_duration(BACKEND, 0.001, fun_name="jit(f)")
        snap = startup.snapshot()
        assert len(snap["events"]) == 5 and snap["dropped"] == 3
        assert startup.report()["dropped"] == 3

    def test_spans_nest_by_thread(self):
        def other_thread():
            with startup.span("state_init"):
                pass

        with startup.span("engine_build") as build:
            with startup.span("cache_alloc") as alloc:
                alloc.set(bytes=4096)
                worker = threading.Thread(target=other_thread)
                worker.start()
                worker.join()
        assert _one("cache_alloc")["parent"] == build.id
        assert _one("cache_alloc")["attrs"] == {"bytes": 4096}
        assert _one("engine_build")["parent"] is None
        assert _one("state_init")["parent"] is None  # its own thread's stack

    def test_report_counts_overlapping_spans_once(self):
        t = time.perf_counter()
        for a, b in ((0.0, 2.0), (0.5, 1.0), (1.5, 3.0)):
            startup._record(startup._new_id(), "jit_trace", t + a, t + b,
                            None, {"fun": "f"})
        startup._record(startup._new_id(), "backend_compile", t + 4.0,
                        t + 5.0, None, {"fun": "f", "cache_hit": False})
        rep = startup.report()
        assert rep["seconds"] == {"jit_trace": 3.0, "backend_compile": 1.0}
        assert rep["program_s"] == 4.0
        assert rep["executables"] == 1 and rep["cache_misses"] == 1
        assert rep["slowest"] == [["f", 1.0, False]]


class TestReady:
    def test_first_call_counts_and_not_inside_a_startup_span(self):
        said = []
        startup.on_ready(said.append)
        with startup.span("warmup"):
            startup.ready("engine")  # a warm-up's own first token
        assert startup.snapshot()["ready"] == {} and not said
        startup.ready("engine")
        first = startup.snapshot()["ready"]["engine"]
        startup.ready("engine")
        assert startup.snapshot()["ready"] == {"engine": first}
        assert said == ["engine"]
        assert _one("ready")["attrs"] == {"scope": "engine"}
        assert startup.report()["ready_s"]["engine"] > 0

    def test_ready_line_is_the_report(self, capsys):
        startup.on_ready(startup.say_ready)
        _compile_of("decode_paged")
        startup.ready("engine")
        line = capsys.readouterr().err.strip()
        assert line.startswith("ready ")
        said = json.loads(line[len("ready "):])
        assert said["scope"] == "engine" and said["executables"] == 1
        assert said == {"scope": "engine", **startup.report()}

    def test_a_compile_after_ready_has_a_name(self):
        """A fresh shape after ``ready``: ``compile_after_ready`` names
        the function with its three durations, ``compiles_after_ready``
        counts it, in the record and in an enabled recorder."""
        rec = obs.enable(obs.Recorder())

        def late_shape(x):
            return x * 3 + 1

        f = jax.jit(late_shape)
        f(jnp.ones((3,)))
        assert not _events("compile_after_ready")
        startup.ready("engine")
        f(jnp.ones((3,)))  # cached: nothing
        assert not _events("compile_after_ready")
        f(jnp.ones((5,)))  # the fresh shape
        late = [e for e in _events("compile_after_ready")
                if e["attrs"]["fun"] == "late_shape"]
        assert len(late) == 1
        attrs = late[0]["attrs"]
        assert set(attrs) == {"fun", "jit_trace_s", "jit_lower_s",
                              "backend_compile_s", "cache_hit"}
        assert attrs["backend_compile_s"] > 0 and attrs["jit_trace_s"] > 0
        assert startup.snapshot()["compiles_after_ready"]["late_shape"] == 1
        assert startup.report()["compiles_after_ready"]["late_shape"] == 1
        summ = rec.summary()
        assert summ["instants"]["compile_after_ready"] >= 1
        assert ({"fun": "late_shape"}, 1.0) in list(
            rec.counter_items("compiles_after_ready"))

    def test_another_engines_warmup_is_startup_not_a_late_compile(self):
        startup.ready("engine")
        with startup.span("warmup"):
            _compile_of("decode_paged")
        assert not _events("compile_after_ready")
        _compile_of("decode_paged")
        assert len(_events("compile_after_ready")) == 1


class TestCompileWatch:
    def test_detects_by_events_and_never_probes_the_cache(self):
        assert "_cache_size" not in inspect.getsource(roofline.CompileWatch)
        assert not hasattr(roofline.CompileWatch, "cache_size")

        class Probed:
            """Counts, and would give the old detector its growth."""

            def __init__(self):
                self.probes = 0

            def _cache_size(self):
                self.probes += 1
                return self.probes

            def __call__(self, x):
                return x

        fn = Probed()
        watch = roofline.CompileWatch(expected=1)
        assert watch.call("step", fn, 3) == 3
        assert fn.probes == 0 and watch.compiles == 0

    def test_compile_span_has_module_attrs_and_children(self):
        def decode_paged(x):
            return x + 1

        watch = roofline.CompileWatch(expected=1, scope="unit")
        watch.call("decode", jax.jit(decode_paged), jnp.ones((4,)), count=2)
        comp = _one("compile")
        assert comp["attrs"] == {"phase": "decode", "scope": "unit",
                                 "count": 2, "module": "jit_decode_paged"}
        kids = _children(comp)
        assert [k["name"] for k in kids if k["attrs"]["fun"] == "decode_paged"
                ] == ["jit_trace", "jit_lower", "backend_compile",
                      "first_run"]
        run = kids[-1]
        assert run["name"] == "first_run" and run["end"] == comp["end"]
        assert run["start"] == [k for k in kids
                                if k["name"] == "backend_compile"][-1]["end"]
        assert watch.events[-1]["fun"] == "decode_paged"

    def test_unpinned_call_spans_without_counting(self):
        watch = roofline.CompileWatch(expected=1)
        watch.call("prefill", jax.jit(lambda x: x * 2), jnp.ones((2,)),
                   pinned=False)
        assert len(_events("compile")) == 1 and watch.compiles == 0

    def test_nested_watch_is_passive(self):
        """hardened_loop's watch round a step that watches itself: one
        ``compile`` span, the outer's."""
        inner_seen = []

        def step(x):
            with startup.Watch() as inner:
                out = jax.jit(lambda y: y - 1)(x)
            inner_seen.append((inner.active, inner.compiled))
            return out

        watch = roofline.CompileWatch(expected=1, scope="train")
        watch.call("step", step, jnp.ones((3,)))
        assert inner_seen == [(False, False)]
        assert watch.compiles == 1 and len(_events("compile")) == 1

    def test_forced_recompile_after_ready_names_its_function(self, params):
        """The acceptance pin: a compile forced after ``ready`` names its
        function in ``compile_after_ready`` and ``unexpected_recompile``."""
        rec = obs.Recorder()
        with obs.local_recorder(rec):
            engine = Engine(CFG, params, slots=2, max_len=32, prefill_len=8)
            warm_engine(engine)
            assert "engine" in startup.snapshot()["ready"]
            sent = obs.Sentinel(phases=("decode", "prefill"), warmup=2)
            server = Server(engine, sentinel=sent)
            engine._decode_paged_jit.clear_cache()  # the injection
            server.submit(Request(rid=0, prompt=[5, 9], max_new_tokens=3))
            server.run()
        assert engine.compile_watch.unexpected == 1
        (a,) = [x for x in sent.report()["anomalies"]
                if x["kind"] == "unexpected_recompile"]
        assert a["fun"] == "decode_paged" and a["metric"] == "decode"
        late = [e["attrs"] for e in _events("compile_after_ready")
                if e["attrs"]["fun"] == "decode_paged"]
        assert len(late) == 1
        assert server.stats()["startup"]["compiles_after_ready"][
            "decode_paged"] == 1


ENGINE_STEPS = ("decode_paged", "prefill_paged", "copy_page", "chunk_rows",
                "gather_page", "scatter_page")


def _assert_steps_under_named_compiles():
    """Every backend compile of a ``_jit_as`` step lies under a
    ``compile`` span that names its module."""
    by_id = {e["id"]: e for e in _events()}
    steps = [e for e in _events("backend_compile")
             if e["attrs"]["fun"] in ENGINE_STEPS]
    for built in steps:
        comp = by_id[built["parent"]]
        assert comp["name"] == "compile"
        assert comp["attrs"]["module"] == "jit_" + built["attrs"]["fun"]
        assert comp["attrs"]["scope"] == "engine"
        names = [k["name"] for k in _children(comp)]
        for want in ("jit_trace", "jit_lower", "backend_compile",
                     "first_run"):
            assert want in names, (comp["attrs"], names)
    return steps


class TestEngineStartup:
    def test_warm_engine_leaves_the_layers_of_setup(self, params):
        engine = Engine(CFG, params, slots=2, max_len=32, prefill_len=8)
        assert not obs.enabled()  # the record needs no recorder
        warm_engine(engine, register_costs=True)
        build, alloc, warm = (_one(n) for n in (
            "engine_build", "cache_alloc", "warmup"))
        assert alloc["parent"] == build["id"]
        assert alloc["attrs"]["bytes"] == sum(
            leaf.nbytes for leaf in jax.tree.leaves(engine.cache))
        compiles = _events("compile")
        assert sorted(c["attrs"]["module"] for c in compiles) == [
            "jit_copy_page", "jit_decode_paged", "jit_prefill_paged"]
        assert all(c["parent"] == warm["id"] for c in compiles)
        assert {c["attrs"]["phase"] for c in compiles} == {
            "prefill", "decode", "copy_page"}
        steps = _assert_steps_under_named_compiles()
        assert len(steps) == 3 == engine.compile_watch.compiles
        # The cost query's second compile of each step has its price.
        queries = _events("cost_query")
        assert [q["attrs"]["phase"] for q in queries] == ["prefill", "decode"]
        assert all(q["parent"] == warm["id"] for q in queries)
        ready = _one("ready")
        assert ready["attrs"] == {"scope": "engine"}
        assert ready["start"] >= warm["end"]
        rep = startup.report()
        assert rep["seconds"]["warmup"] >= rep["seconds"]["compile"]
        assert rep["program_s"] >= rep["seconds"]["warmup"]
        assert rep["executables"] == len(_events("backend_compile"))

    def test_compacted_steps_say_their_count(self, params, monkeypatch):
        monkeypatch.setattr(engine_module, "_WEIGHT_BOUND_ROWS", 8)
        monkeypatch.setattr(engine_module, "_COMPACT_ROWS", 32)
        engine = Engine(CFG, params, slots=4, max_len=64, kv_page_size=8,
                        prefill_chunk=8, decode_attention="reference")
        warm_engine(engine)
        assert engine._prefill_counts == (1, 2, 4)
        assert engine.compile_watch.compiles == 5  # the pin counts no helper
        by_module: dict = {}
        for comp in _events("compile"):
            by_module.setdefault(comp["attrs"]["module"], []).append(
                comp["attrs"].get("count"))
        assert sorted(by_module["jit_prefill_paged"]) == [1, 2, 4]
        assert sorted(by_module["jit_chunk_rows"]) == [1, 2, 4]
        _assert_steps_under_named_compiles()

    def test_an_unwarmed_server_is_ready_with_its_first_token(self, params):
        engine = Engine(CFG, params, slots=2, max_len=32, prefill_len=8)
        server = Server(engine)
        server.submit(Request(rid=0, prompt=[5, 9, 3], max_new_tokens=3))
        assert "engine" not in startup.snapshot()["ready"]
        server.run()
        assert "engine" in startup.snapshot()["ready"]
        stats = server.stats()
        assert stats["startup"] == startup.report()
        assert stats["startup"]["ready_s"]["engine"] > 0


class TestTrainStartup:
    @staticmethod
    def _build(world):
        from mpit_tpu import opt as gopt
        from mpit_tpu.train import make_train_step

        def loss(p, b):
            return jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2), {}

        params = {"w": jnp.eye(16) * 0.1}
        init_fn, step_fn, _ = make_train_step(
            loss, gopt.goo(0.1, 0.0), world, zero1=True)
        x = np.ones((32, 16), np.float32)
        return init_fn, step_fn, params, {"x": x, "y": x}

    def _assert_one_train_compile(self):
        comp = _one("compile")
        assert comp["attrs"] == {"phase": "step", "scope": "train",
                                 "module": "jit_train_step"}
        names = [k["name"] for k in _children(comp)
                 if k["attrs"]["fun"] == "train_step"]
        assert names == ["jit_trace", "jit_lower", "backend_compile",
                         "first_run"]
        ready = _one("ready")
        assert ready["attrs"] == {"scope": "train"}
        return comp

    def test_first_call_leaves_state_init_and_a_compile(self):
        world = mpit_tpu.init({"data": -1}, set_default=False)
        init_fn, step_fn, params, batch = self._build(world)
        state = init_fn(params)
        init = _one("state_init")
        assert any(k["name"] == "backend_compile" for k in _children(init))
        state, _ = step_fn(state, batch)
        comp = self._assert_one_train_compile()
        assert comp["parent"] is None and comp["start"] >= init["end"]
        step_fn(state, batch)  # warm: nothing more
        assert len(_events("compile")) == 1

    def test_under_hardened_loop_the_loops_watch_records_it(self):
        from mpit_tpu.train import MetricLogger, hardened_loop

        world = mpit_tpu.init({"data": -1}, set_default=False)
        init_fn, step_fn, params, batch = self._build(world)
        out = hardened_loop(
            world, init_fn(params), step_fn, iter([batch] * 4), steps=4,
            log_every=2, logger=MetricLogger(stdout=False))
        assert out["compiles"] == 1
        self._assert_one_train_compile()
