"""Process-level JAX set-up shared by the entry points.

Two decisions every entry point makes the same way, from a ``main`` and
never at import:

* where JAX's persistent compile cache lives (:func:`configure_compile_cache`);
* how a ``--mesh N`` run gets its N devices (:func:`cpu_device_env`,
  :func:`mesh_devices`).  Only a CPU run can emulate devices, and the flag
  that does so must be set before JAX initializes a backend, so the choice
  is read from ``JAX_PLATFORMS`` before anything touches a device.  On an
  accelerator every device is a chip: a mesh larger than the host is an
  error, never a silent fall-back to the CPU.
"""

from __future__ import annotations

import os
import re
from pathlib import Path
from typing import Optional

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"
_DEVICE_FLAG = "--xla_force_host_platform_device_count"


def configure_compile_cache() -> str:
    """Point JAX's persistent compile cache at a fixed directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX; otherwise the
    cache is ``<checkout>/.jax_cache``.  The path never depends on a
    temporary directory, a process id or the time, so the next run finds
    what this one compiled.  Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def cpu_device_env(devices: int) -> Optional[dict]:
    """Environment for a child process with ``devices`` host devices, or
    ``None`` when this process needs no child.

    A child is needed only on the CPU (``JAX_PLATFORMS=cpu``) when
    ``XLA_FLAGS`` does not already ask for enough host devices.
    """
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        return None
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(_DEVICE_FLAG + r"=(\d+)", flags)
    if m and int(m.group(1)) >= devices:
        return None
    return dict(os.environ,
                XLA_FLAGS=f"{flags} {_DEVICE_FLAG}={devices}".strip())


def mesh_devices(n: int) -> list:
    """The first ``n`` devices; an error when the backend has fewer."""
    import jax
    devices = jax.devices()
    if len(devices) < n:
        hint = ("" if jax.default_backend() != "cpu" else
                " (set JAX_PLATFORMS=cpu to emulate host devices)")
        raise SystemExit(f"a {n}-device mesh needs {n} devices; the "
                         f"{jax.default_backend()} backend has "
                         f"{len(devices)}{hint}")
    return devices[:n]
