"""Process-level JAX set-up shared by every entry point: where the compile
cache lives, and which device the process landed on.

Two rules the entry points (``server/api_http.py``, the chip
tools, ``__graft_entry__.py``, ``tests/conftest.py``) all follow:

- **The compile cache is placed from outside.**  ``JAX_COMPILATION_CACHE_DIR``
  wins when set (JAX reads it itself; nothing is set in code).  Otherwise the
  cache sits at a FIXED path inside the checkout — the path is part of the
  cache key's environment, so a temp name, a pid or a time would never hit —
  and the variable is exported so child processes land in the same place.
- **No silent CPU.**  A process meant for an accelerator fails when JAX
  falls back to the CPU backend; the CPU serves only when asked for by name
  (``--platform cpu`` or ``JAX_PLATFORMS`` naming it — the tests do both).
"""

from __future__ import annotations

import logging
import os
from dataclasses import dataclass

logger = logging.getLogger(__name__)

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def configure_compile_cache(dirname: str = ".jax_cache") -> str:
    """Point JAX's persistent compile cache at its one place; returns it.

    Call before the first compilation.  ``dirname`` is the directory name
    under the checkout used when ``JAX_COMPILATION_CACHE_DIR`` is unset
    (the test suite passes ``.jax_cache_tests``)."""
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    import jax

    path = os.path.join(CHECKOUT, dirname)
    # Exported for children; config.update for THIS process, whose jax may
    # have been imported (and have read the environment) already.
    os.environ[CACHE_ENV] = path
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@dataclass(frozen=True)
class DeviceInfo:
    platform: str
    device_kind: str
    count: int


def _landed() -> DeviceInfo:
    """Initialises the backend (first call) and reports it, logged once per
    entry point."""
    import jax

    devices = jax.devices()
    info = DeviceInfo(devices[0].platform, devices[0].device_kind,
                      len(devices))
    logger.info("device: platform=%s device_kind=%s count=%d",
                info.platform, info.device_kind, info.count)
    return info


def _cpu_named(requested: str | None) -> bool:
    if requested == "cpu":
        return True
    named = os.environ.get("JAX_PLATFORMS", "").lower().split(",")
    return "cpu" in (p.strip() for p in named)


def resolve_platform(requested: str | None = None) -> DeviceInfo:
    """Initialise the JAX backend ONCE and say where the process landed.

    ``requested`` (``cpu`` / ``tpu``) pins the platform; an unavailable one
    raises from JAX's own backend init.  Landing on the CPU without having
    named it exits non-zero: an entry point that wanted a chip must not
    carry on at CPU speed under a device metric's name."""
    import jax

    if requested:
        jax.config.update("jax_platforms", requested)
    info = _landed()
    if info.platform == "cpu" and not _cpu_named(requested):
        raise SystemExit(
            "no accelerator: JAX found no TPU and fell back to the cpu "
            "backend. To run on the CPU say so by name (--platform cpu or "
            "JAX_PLATFORMS=cpu).")
    return info


def require_accelerator(what: str) -> DeviceInfo:
    """For chip benchmarks and checks: fail unless the backend is a TPU.

    A number from a CPU run is never a device metric, so these tools have
    no CPU mode at all — naming the CPU does not help them."""
    info = _landed()
    if info.platform != "tpu":
        raise SystemExit(
            f"{what} needs a TPU: JAX found platform={info.platform} "
            f"device_kind={info.device_kind!r} and no tpu device. Run it "
            "on the chip.")
    return info
