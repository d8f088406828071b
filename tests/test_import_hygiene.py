"""Import hygiene for the host-pure hot-path modules (ISSUE 8 satellite).

``obs.stream``, ``obs.slo``, ``obs.startup`` and ``serve.loadgen`` are the "pure host
python in the hot path" layer: the serve scheduler feeds them per
tick/request, and the CLI imports them at startup. Their claim — no
jax, no numpy at module level — is what keeps disabled-overhead near
zero and CLI startup cheap, and nothing pinned it until now: a future
edit adding one convenience ``import numpy`` at the top would regress
both silently.

The pin is a REAL import in a subprocess, with the package ``__init__``
chain stubbed out: the packages themselves legitimately import
jax-heavy siblings (``mpit_tpu/__init__`` pulls comm, ``obs/__init__``
pulls the numpy exporters), so the claim under test is about the
modules and their own module-level import closure — which the stubbed
import executes exactly.
"""

from __future__ import annotations

import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]

_SCRIPT = textwrap.dedent(
    """
    import sys, types

    root = sys.argv[1]
    # Stub the package inits (they import jax-heavy siblings); the
    # submodule imports below then execute ONLY the modules under test
    # plus whatever THEY import at module level.
    for name, path in (
        ("mpit_tpu", root + "/mpit_tpu"),
        ("mpit_tpu.obs", root + "/mpit_tpu/obs"),
        ("mpit_tpu.serve", root + "/mpit_tpu/serve"),
    ):
        mod = types.ModuleType(name)
        mod.__path__ = [path]
        sys.modules[name] = mod
        if "." in name:  # pre-seeded parents never get the attr set
            parent, _, child = name.rpartition(".")
            setattr(sys.modules[parent], child, mod)

    import mpit_tpu.obs.stream
    import mpit_tpu.obs.slo
    import mpit_tpu.obs.startup
    import mpit_tpu.serve.loadgen

    heavy = sorted(
        m for m in ("jax", "jaxlib", "numpy", "flax") if m in sys.modules
    )
    assert not heavy, f"hot-path modules imported heavy deps: {heavy}"

    # The modules are functional, not just importable: one windowed
    # observation and a spec parse run on stdlib alone.
    reg = mpit_tpu.obs.stream.StreamRegistry(window_s=1.0, clock=lambda: 0.5)
    reg.observe("ttft", 0.25)
    assert reg.quantile("ttft", 0.5) is not None
    spec = mpit_tpu.serve.loadgen.parse_load_spec("rate=8,process=bursty")
    assert spec.rate == 8.0 and spec.process == "bursty"
    # The start-up record fills and rolls up before jax is imported
    # (install() alone imports jax.monitoring, when it is called).
    with mpit_tpu.obs.startup.span("engine_build"):
        pass
    assert mpit_tpu.obs.startup.report()["seconds"].keys() == {"engine_build"}
    assert not any(
        m in sys.modules for m in ("jax", "jaxlib", "numpy", "flax")
    )
    print("CLEAN")
    """
)


class TestHotPathImportHygiene:
    def test_stream_slo_loadgen_import_without_jax_or_numpy(self):
        out = subprocess.run(
            [sys.executable, "-c", _SCRIPT, str(REPO)],
            capture_output=True,
            text=True,
            timeout=120,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        assert "CLEAN" in out.stdout

    def test_loadgen_trace_generation_still_deterministic(self):
        """The hygiene refactor moved numpy INSIDE generate_arrivals —
        the pinned (spec, seed) determinism must be untouched."""
        from mpit_tpu.serve.loadgen import LoadSpec, generate_arrivals

        a = generate_arrivals(
            LoadSpec(rate=20.0), vocab_size=100, duration_s=1.0, seed=7
        )
        b = generate_arrivals(
            LoadSpec(rate=20.0), vocab_size=100, duration_s=1.0, seed=7
        )
        assert [x.t for x in a] == [x.t for x in b]
        assert [x.request.prompt for x in a] == [x.request.prompt for x in b]


class TestOneServingEngine:
    """ISSUE 28: the package holds no second engine. ``Engine`` serves
    through the page pool and nothing selects that; the oracle of a
    serving test is the no-cache forward (``tests/test_serve.py::
    ref_greedy``) or ``benchmark/reference.py``, never a cache of another
    layout kept "just as a reference"."""

    GONE = ("KVCache", "alloc_cache", "cache_specs")

    def test_serve_exports_no_per_slot_cache(self):
        import mpit_tpu.serve as serve
        from mpit_tpu.serve import kvcache

        for name in self.GONE:
            assert not hasattr(serve, name), name
            assert not hasattr(kvcache, name), name
            assert name not in serve.__all__ + kvcache.__all__
        assert {"PagedKVCache", "alloc_paged_cache", "paged_cache_specs",
                "PageAllocator"} <= set(serve.__all__)

    def test_nothing_selects_the_engine(self):
        import inspect

        from mpit_tpu.models.serving import ServeModel
        from mpit_tpu.ops import decode_attention
        from mpit_tpu.serve import Engine, Server

        import re

        for cls in (Engine, Server):
            # An attribute named so, not a method like ``_paged_decode_step``.
            assert not re.search(r"self\._?paged\b", inspect.getsource(cls))
        assert not hasattr(Engine, "prefill")  # the whole-prompt step
        options = inspect.signature(Engine.__init__).parameters
        assert "paged" not in options and options["kv_pages"].default is None
        assert not hasattr(ServeModel, "forward_cached")
        for method in (ServeModel.with_decode_attention,
                       ServeModel.check_supported):
            assert "paged" not in inspect.signature(method).parameters
        assert not hasattr(decode_attention, "flash_decode_attention")
