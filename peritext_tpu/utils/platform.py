"""Platform pinning for virtual-mesh (CPU) runs, and the compile cache.

The multi-chip paths are exercised on N virtual CPU devices
(``--xla_force_host_platform_device_count``), which requires two things to
happen *before any jax backend initializes*:

* the forced host device count must be in ``XLA_FLAGS`` (XLA parses it once,
  at CPU-client creation), and
* the default platform must be pinned to ``cpu`` (``JAX_PLATFORMS`` and
  ``jax.config``), so an eager array created on the default backend never
  initializes a TPU client the CPU mesh does not need — and that another
  process may hold.

The driver entry point and the scaling scripts share this logic; keep fixes
here so they reach all of them.  Two sites intentionally differ:
``tests/conftest.py`` hand-rolls the env part (it must run before pytest
imports anything else), and the fuzz CLI's ``--mesh`` mode honors an
existing ``JAX_PLATFORMS`` instead of forcing CPU (mesh fuzz may target real
chips).
"""

from __future__ import annotations

import contextlib
import os
import re
from typing import Iterator, List

_COUNT_RE = re.compile(r"--xla_force_host_platform_device_count=(\d+)")

#: the fixed compile-cache directory when JAX_COMPILATION_CACHE_DIR is unset
#: (the path is part of the cache key, so it must not move between runs)
REPO_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory (jax reads
    it itself) and no other is set; otherwise the fixed repo-local
    :data:`REPO_COMPILE_CACHE`.  Every compile is cached, however short.
    Entry points call this (chip_smoke.py, bench.py's worker, the demos);
    the package import and the tests never do — the recompile-sentinel
    tests count real compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = REPO_COMPILE_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def pin_cpu_platform(n_devices: int) -> List["object"]:
    """Persistently pin the process to CPU with >= ``n_devices`` virtual
    devices and return them.

    Mutates ``XLA_FLAGS`` / ``JAX_PLATFORMS`` / ``jax.config`` for the rest
    of the process (use :func:`cpu_platform` for a restoring variant).
    Raises ``RuntimeError`` if the count cannot be satisfied — which happens
    when a caller already initialized a jax backend, because XLA reads the
    forced count exactly once, at CPU-client creation.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = _COUNT_RE.search(flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    elif int(m.group(1)) < n_devices:
        # An existing smaller count would otherwise win (the substring is
        # present, but too small) and guarantee failure below.
        os.environ["XLA_FLAGS"] = _COUNT_RE.sub(
            f"--xla_force_host_platform_device_count={n_devices}", flags
        )
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:  # graftlint: boundary(config update after backend init raises version-dependent types; the devices check below decides)
        pass  # backends already initialized; devices check below decides

    devices = jax.devices("cpu")
    if len(devices) < n_devices:
        raise RuntimeError(
            f"need {n_devices} CPU devices, have {len(devices)}; a jax "
            "backend initialized before the forced host device count was "
            "set — call this (or set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_devices}) before "
            "any jax use"
        )
    return devices


@contextlib.contextmanager
def cpu_platform(n_devices: int) -> Iterator[List["object"]]:
    """Context manager: CPU default platform with >= ``n_devices`` virtual
    devices; eager arrays inside the block land on the first CPU device.

    Restores ``JAX_PLATFORMS`` / ``XLA_FLAGS`` / ``jax.config`` on exit so a
    healthy-TPU caller can keep using its chip after a CPU-mesh dryrun.
    (The CPU backend itself stays alive, so arrays created inside the block
    remain valid after exit.)
    """
    prev_env = os.environ.get("JAX_PLATFORMS")
    prev_flags = os.environ.get("XLA_FLAGS")

    import jax

    prev_cfg = getattr(jax.config, "jax_platforms", None)
    try:
        devices = pin_cpu_platform(n_devices)
        with jax.default_device(devices[0]):
            yield devices
    finally:
        if prev_env is None:
            os.environ.pop("JAX_PLATFORMS", None)
        else:
            os.environ["JAX_PLATFORMS"] = prev_env
        if prev_flags is None:
            os.environ.pop("XLA_FLAGS", None)
        else:
            os.environ["XLA_FLAGS"] = prev_flags
        try:
            jax.config.update("jax_platforms", prev_cfg)
        except Exception:  # graftlint: boundary(best-effort restore mirrors pin_cpu_platform's tolerant update)
            pass
