"""mpit_tpu.utils — observability and accounting utilities.

Where the reference's observability is per-rank prints and ad-hoc wall
timers in its Lua scripts (SURVEY.md §6), this package provides the
TPU-native toolkit: profiler traces, blocking step timers, XLA cost
analysis, roofline estimates, and collective-traffic models.
"""

from mpit_tpu.utils.aot import (
    abstract_state,
    abstractify,
    aot_compile,
    memory_report,
    topology_devices,
    topology_world,
)
from mpit_tpu.utils.compile_cache import compile_cache_dir
from mpit_tpu.utils.profiling import (
    ChipSpec,
    CommModel,
    StepTimer,
    TPU_V5E,
    allreduce_gbps,
    chip_spec_for,
    collective_bytes,
    compiled_cost,
    modeled_all_gather_seconds,
    modeled_allreduce_seconds,
    modeled_reduce_scatter_seconds,
    roofline,
    scaling_projection,
    trace,
    tree_bytes,
)

__all__ = [
    "abstract_state",
    "abstractify",
    "aot_compile",
    "memory_report",
    "topology_devices",
    "topology_world",
    "compile_cache_dir",
    "ChipSpec",
    "CommModel",
    "StepTimer",
    "TPU_V5E",
    "allreduce_gbps",
    "chip_spec_for",
    "collective_bytes",
    "compiled_cost",
    "modeled_all_gather_seconds",
    "modeled_allreduce_seconds",
    "modeled_reduce_scatter_seconds",
    "roofline",
    "scaling_projection",
    "trace",
    "tree_bytes",
]
