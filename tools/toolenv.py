"""Shared CPU-run preamble for the repo's standalone tools.

Every bench/report script used to copy-paste the same block: put the
repo root on ``sys.path`` and pin ``JAX_PLATFORMS=cpu`` (optionally with
N simulated host devices) before jax is imported. This module is the one
copy (same settings as tests/conftest.py).

Usage, FIRST thing in a tool (the script's own directory is on
``sys.path`` when run as ``python tools/<name>.py``)::

    import toolenv
    toolenv.force_cpu()            # or force_cpu(devices=8)
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def repo_root() -> str:
    return REPO


def force_cpu(devices: int = 0) -> None:
    """Pin this process to the CPU backend (``devices`` > 0 additionally
    forces an N-device simulated host platform). Must run before jax is
    imported; an explicit ``JAX_PLATFORMS`` in the environment wins."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if devices:
        xla_flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in xla_flags:
            os.environ["XLA_FLAGS"] = (
                xla_flags
                + f" --xla_force_host_platform_device_count={devices}"
            ).strip()
