"""Early pytest plugin: put the test run on eight virtual CPU devices.

The collective and sharding suites need a mesh of several devices — the
analogue of the reference running MPI locally under ``mpirun -n 2..4``
(SURVEY.md §5.1, §5.2). The CPU backend makes as many devices as
``--xla_force_host_platform_device_count`` says, and reads it once, when
it starts — so the flag has to be in ``XLA_FLAGS`` before jax is
imported. Loaded via ``pytest.ini`` ``addopts = -p reexec_cpu``, this
module runs at plugin registration, ahead of every conftest and test
module; ``tests/conftest.py`` calls :func:`use_cpu_mesh` too, for runs
that bypass ``pytest.ini``.

Set ``MPIT_TEST_PLATFORM=tpu`` to leave the environment alone and run on
the attached chip instead.
"""

import os
import re
import sys

N_FAKE_DEVICES = 8

_COUNT_FLAG = r"--xla_force_host_platform_device_count=(\d+)"


def forced_device_count(flags: str) -> int | None:
    """The host-platform device count an ``XLA_FLAGS`` string asks for."""
    m = re.search(_COUNT_FLAG, flags)
    return int(m.group(1)) if m else None


def cpu_mesh_env(n_devices: int | None = None) -> dict:
    """A copy of ``os.environ`` rewritten for a virtual-CPU-mesh process.

    Pins ``JAX_PLATFORMS=cpu`` and sets the host-platform device count.
    An explicit ``n_devices`` replaces any pre-existing
    ``xla_force_host_platform_device_count`` flag; ``None`` preserves a
    caller-supplied count (defaulting to ``N_FAKE_DEVICES`` when none is
    set).
    """
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if n_devices is None:
        n_devices = forced_device_count(flags) or N_FAKE_DEVICES
    flags = re.sub(_COUNT_FLAG, "", flags)
    flags += f" --xla_force_host_platform_device_count={n_devices}"
    env["XLA_FLAGS"] = flags.strip()
    return env


def use_cpu_mesh() -> None:
    """Point THIS process at the virtual CPU mesh (before jax starts)."""
    if os.environ.get("MPIT_TEST_PLATFORM", "cpu") != "cpu":
        return
    # None: honor a caller-supplied device count.
    os.environ.update(cpu_mesh_env())


# Auto-run only when pytest is actually driving this process (the
# ``-p reexec_cpu`` early-plugin path: argv[0] is the pytest console script
# or pytest's __main__.py under ``python -m pytest``). Scripts import this
# module for ``cpu_mesh_env`` alone, and must keep their own environment.
_argv0 = sys.argv[0]
if os.path.basename(_argv0).startswith(("pytest", "py.test")) or _argv0.endswith(
    os.path.join("pytest", "__main__.py")
):
    use_cpu_mesh()
