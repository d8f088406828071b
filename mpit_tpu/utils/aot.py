"""AOT compilation against a real TPU topology — no hardware required.

Everything multi-chip in this environment runs under CPU fake-mesh
simulation (SURVEY.md §5.2): correct for protocol/semantics, structurally
blind to what the real TPU compiler does — Mosaic lowering rejections,
layout-pass tile padding (the ZeRO-1 16x blow-up bench.py r3 hit), VMEM
budgets. JAX's topology-based AOT path closes that gap: build a
:class:`~jax.sharding.Mesh` from ``jax.experimental.topologies`` device
proxies for a real chip topology (e.g. ``v5e:2x4``), ``.lower()`` the
jitted program against abstract sharded arguments, and ``.compile()`` it
with the real TPU compiler. Nothing executes; compile errors and
``memory_analysis()`` are the product.

The reference could not do this at all — an MPI program's resource
behavior is only observable by running it on the cluster (SURVEY.md §5.1:
"MPI itself run locally is the fake cluster"). AOT-against-topology is the
TPU-native upgrade: the compiler is a queryable model of the machine.

Used by ``compile_multichip.py`` (repo root, driver-runnable) and the
``tests/test_aot.py`` memory-regression tests.

Known limitation (round-5): ``jax.experimental.topologies`` describes a
single ICI-connected slice — there is no public topology spec for a
multi-slice (DCN-joined) system, so true cross-slice programs cannot be
AOT-compiled as such. The hybrid-mesh phase therefore compiles the
slice-major program against VIRTUAL slices (contiguous halves of one
real topology, ``comm.mesh._slice_groups``'s documented fallback): mesh
layout, collective decomposition, and memory are those of the
multi-slice program; DCN link characteristics are invisible to the
compiler either way (it prices collectives by topology, not by
measured link speed).
"""

from __future__ import annotations

from typing import Any, Mapping, Sequence

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

DEFAULT_TOPOLOGY = "v5e:2x4"  # one v5e host: 8 chips, the pod building block


def topology_devices(topology: str = DEFAULT_TOPOLOGY) -> Sequence[Any]:
    """Device proxies for ``topology`` (no hardware attached).

    Requires the TPU compiler (libtpu) in the installation; no chip
    needs to be attached.
    """
    from jax.experimental import topologies

    return topologies.get_topology_desc(topology, platform="tpu").devices


def topology_world(
    axis_shapes: Mapping[str, int], topology: str = DEFAULT_TOPOLOGY
):
    """A :class:`mpit_tpu.comm.World` whose mesh spans topology proxies.

    Every ``make_*_train_step`` accepts it like a live world; only
    ``.lower()``/``.compile()`` are valid on the resulting jits (executing
    would need the actual chips).
    """
    import mpit_tpu

    return mpit_tpu.init(
        dict(axis_shapes), devices=topology_devices(topology), set_default=False
    )


def abstractify(tree, mesh, specs=None):
    """ShapeDtypeStructs (+ NamedShardings) for ``jit.lower``.

    ``specs`` is a matching pytree of PartitionSpecs (or one spec for all
    leaves; default replicated). ``tree`` may hold arrays or
    ShapeDtypeStructs.
    """
    if specs is None or isinstance(specs, P):
        one = specs if isinstance(specs, P) else P()
        specs = jax.tree.map(lambda _: one, tree)

    def to_abstract(leaf, spec):
        return jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=NamedSharding(mesh, spec)
        )

    return jax.tree.map(to_abstract, tree, specs)


def abstract_state(init_fn, params, specs, mesh):
    """Abstract TrainState for a tier: ``eval_shape`` the tier's host-level
    ``init_fn`` (no FLOPs, no devices) and attach the tier's own
    PartitionSpecs."""
    shapes = jax.eval_shape(init_fn, params)
    return abstractify(shapes, mesh, specs)


def memory_report(compiled) -> dict:
    """Compiled-memory numbers (bytes) the regression tests assert on."""
    ma = compiled.memory_analysis()
    return {
        "temp_bytes": int(ma.temp_size_in_bytes),
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "generated_code_bytes": int(ma.generated_code_size_in_bytes),
    }


def aot_compile(jitted, *abstract_args):
    """Lower + compile ``jitted`` for the args' (topology) mesh; returns the
    ``jax.stages.Compiled`` — call :func:`memory_report` on it."""
    return jitted.lower(*abstract_args).compile()
