"""Forced-CPU subprocess environment — the one shared recipe.

Every place this repo spawns a fresh Python interpreter that imports jax
(multiprocess collective tests, streaming producers, fleet workers) needs
the SAME environment surgery, applied BEFORE the child's first jax import:

- ``JAX_PLATFORMS=cpu`` — pin the CPU backend by env, before the child
  imports jax. A chip belongs to one process at a time: a parent that has
  touched jax holds it, and a child that reached for it would fail or hang,
  so every child this repo spawns is a CPU process.
- ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — size the
  child's virtual CPU mesh. Any existing count in inherited flags is
  REWRITTEN, not appended: duplicate flags make XLA take the first one,
  which silently builds the parent's mesh size. Unrelated inherited
  XLA flags (e.g. a persistent-cache knob) are preserved.
- drop ``JAX_NUM_PROCESSES`` — a child is a single-process world unless
  it calls ``jax.distributed.initialize`` itself.

This used to live as a private copy in ``tests/test_multiprocess.py`` /
``tests/helpers/multiproc_worker.py``; the fleet worker spawner made a
third copy inevitable, so it is a package helper now (ISSUE 13).
"""

from __future__ import annotations

import os
import re
import socket
from typing import Dict, Optional

__all__ = ["forced_cpu_env", "free_port"]

_DEVCOUNT_RE = re.compile(r"--xla_force_host_platform_device_count=(\d+)")


def forced_cpu_env(local_devices: int = 1,
                   base: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """A copy of ``base`` (default: ``os.environ``) with the CPU backend
    forced for a child interpreter: platform pinned to cpu, the virtual
    device count set to ``local_devices``."""
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    want = f"--xla_force_host_platform_device_count={int(local_devices)}"
    if _DEVCOUNT_RE.search(flags):
        flags = _DEVCOUNT_RE.sub(want, flags)
    else:
        flags = (flags + " " + want).strip()
    env["XLA_FLAGS"] = flags
    env.pop("JAX_NUM_PROCESSES", None)
    return env


def free_port() -> int:
    """An OS-assigned free TCP port (racy by nature — bind promptly)."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]
