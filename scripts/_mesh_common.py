"""Shared boilerplate for the scripts/check_*.py subprocess suites.

Every check script runs as a fresh subprocess (tests/test_distributed.py
`_run`) so it can emulate a multi-device host.  The shared contract:

  * ``force_host_devices(n)`` must run BEFORE anything imports jax —
    XLA reads the flag at backend init.  This module therefore imports
    nothing heavier than os/sys at module scope.
  * ``check(name, ok, info)`` prints one "PASS name"/"FAIL name" line per
    assertion (the test harness greps stdout for "FAIL ").
  * ``finish()`` prints the "ALL-OK" sentinel and exits non-zero when any
    check failed.
  * ``mesh_and_spec(shape, axes)`` builds the jax Mesh + MeshSpec pair
    every engine-level check needs.
"""
import os
import sys

FAIL = []


def force_host_devices(n: int = 8) -> None:
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"


def check(name: str, ok, info="") -> bool:
    ok = bool(ok)
    print(("PASS " if ok else "FAIL ") + name, info)
    if not ok:
        FAIL.append(name)
    return ok


def finish() -> None:
    print("ALL-OK" if not FAIL else f"FAILED: {FAIL}")
    sys.exit(0 if not FAIL else 1)


def mesh_and_spec(shape, axes=("data", "model")):
    from repro.core.qsdp import MeshSpec
    from repro.launch.mesh import make_mesh

    return (make_mesh(tuple(shape), tuple(axes)),
            MeshSpec(axes=tuple(axes), shape=tuple(shape)))
