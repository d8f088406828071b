"""Workload launcher: ``python -m mpit_tpu.asyncsgd <workload> [options]``.

The ``mpirun``+rank-role-dispatch analogue (SURVEY.md §3.2 A6): where the
reference starts P identical Lua processes and routes each rank into
``pserver.lua`` or a client training loop by convention, the TPU-native
launcher starts ONE SPMD program over the mesh — rank roles only survive
inside ``--mode parity`` (the compat-simulator path).
"""

from __future__ import annotations

import importlib
import json
import sys

from mpit_tpu.asyncsgd import WORKLOADS


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        print(f"workloads: {', '.join(WORKLOADS)}")
        print("options: see `python -m mpit_tpu.asyncsgd <workload> --help`")
        return 0
    name, rest = argv[0], argv[1:]
    if name not in WORKLOADS:
        print(f"unknown workload {name!r}; choose from {WORKLOADS}", file=sys.stderr)
        return 2
    mod = importlib.import_module(f"mpit_tpu.asyncsgd.{name}")
    out = mod.main(rest)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    from mpit_tpu.obs import startup
    from mpit_tpu.utils import compile_cache_dir

    compile_cache_dir()
    # One ``ready`` line on stderr when the first step's output is.
    startup.install()
    startup.on_ready(startup.say_ready)
    raise SystemExit(main())
