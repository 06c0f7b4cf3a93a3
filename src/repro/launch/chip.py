"""Process roles on a chip host: who may hold the accelerator.

A TPU belongs to one process at a time.  A deployment therefore runs
one chip-holding process (a ``fed_worker``, ``gnn_serve``,
``embed_server --device-tables``, ``train`` or ``serve``); every other
role (``fed_coordinator``, ``embed_server`` without device tables,
``obs_dump``, ``build_store``) calls :func:`pin_cpu` first, so it never
opens the TPU backend and never takes the chip from the process that
needs it.

Chip-holding CLIs call :func:`enable_compile_cache` and then
:func:`announce_device`, whose line (the first the process prints) says
which platform jax actually gave it — a worker that silently fell back
to the CPU shows there.

Nothing here runs at import time; this module does not import jax.
"""

from __future__ import annotations

import os
import pathlib
import sys

#: the persistent compile cache used when ``JAX_COMPILATION_CACHE_DIR``
#: is unset: a fixed path at the repo root (the path is part of the
#: cache key, so it must not move between runs)
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def pin_cpu() -> None:
    """Restrict this process (and the children it starts) to jax's CPU
    backend.  Call before anything initialises a jax backend."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if "jax" in sys.modules:        # imported, but no backend opened yet
        sys.modules["jax"].config.update("jax_platforms", "cpu")


def enable_compile_cache() -> None:
    """Keep jax's persistent compilation cache at :data:`CACHE_DIR`.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it by itself
    and this does nothing."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))


def announce_device(role: str) -> dict:
    """Print ``<role>: platform=... kind=... count=...`` for the devices
    jax opened, and return them as a dict."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"{role}: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    return info
