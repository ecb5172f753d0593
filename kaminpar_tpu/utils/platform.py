"""Lazy, ``JAX_PLATFORMS``-respecting device/backend gate, and the one
place the persistent compile cache is configured.

Eager backend discovery is a hazard: any ``jax.devices()`` /
``jax.default_backend()`` call that runs before (or regardless of) the
platform restriction initializes every registered PJRT plug-in, and a
plug-in whose backend is unreachable can block there for minutes.

This module is the single place the package is allowed to ask jax about
devices/backends (lint rule R2 enforces that; see docs/static_analysis.md):

  * every query is lazy — ``import jax`` happens inside the call, never
    at module import;
  * when ``JAX_PLATFORMS`` (or the package's own ``KAMINPAR_TPU_PLATFORM``)
    names a platform, queries are restricted to that platform explicitly,
    so a misbehaving plug-in is never initialized as a side effect;
  * ``default_backend()`` answers straight from the environment when it
    can, touching no backend at all — the cheapest possible path for
    callers that only branch on "cpu or not" (graphs/csr.shape_floors).

Platform resolution order: ``JAX_PLATFORMS`` wins; ``KAMINPAR_TPU_PLATFORM``
is the package-level override propagated into ``JAX_PLATFORMS`` before
first backend init (for embedding hosts whose environment cannot be
edited after process start).
"""

from __future__ import annotations

import os
import sys
from typing import Optional, Tuple

# last JAX_PLATFORMS value pushed into jax's config (None = never).
# Keyed by value, not a one-shot bool: an embedding host may set the
# override only after earlier gated queries already ran, and the gate
# must pick the change up on the next call.
_synced_value: Optional[str] = None


def ensure_platform_env() -> None:
    """Propagate ``KAMINPAR_TPU_PLATFORM`` into ``JAX_PLATFORMS``.

    Must run before jax initializes a backend; idempotent and free
    afterwards.  Called by every query below and by the C-ABI entry
    (capi.compute_from_pointers) before the pipeline imports.

    When jax is ALREADY imported (importing any kaminpar_tpu module
    pulls it in, and embedding hosts may set the override only just
    before the first compute call), the ``jax_platforms`` config has
    latched the env value from import time — pushing the restriction
    into the live config is the only thing that still works, and it
    does as long as no backend has initialized yet."""
    global _synced_value
    want = os.environ.get("KAMINPAR_TPU_PLATFORM", "").strip()
    if want and not os.environ.get("JAX_PLATFORMS", "").strip():
        os.environ["JAX_PLATFORMS"] = want
    effective = os.environ.get("JAX_PLATFORMS", "").strip()
    if effective == _synced_value:
        return
    _synced_value = effective
    if effective and "jax" in sys.modules:
        try:
            sys.modules["jax"].config.update("jax_platforms", effective)
        except Exception:
            pass  # backends already live: the explicit-backend queries
            # below still restrict every call this package makes


def _backend_init_guard():
    """Watchdog stage for backend discovery, armed only by the explicit
    env ceiling (KAMINPAR_TPU_HARD_DEADLINE_S) — backend init happens
    before any run-scoped budget exists.  Degrades to a no-op context
    while the resilience package is still bootstrapping."""
    try:
        from ..resilience import supervisor

        return supervisor.stage_guard(
            "backend-init", supervisor.env_ceiling()
        )
    except Exception:
        import contextlib

        return contextlib.nullcontext()


def requested_platforms() -> Tuple[str, ...]:
    """Platforms the environment restricts jax to ((), when unrestricted)."""
    ensure_platform_env()
    raw = os.environ.get("JAX_PLATFORMS", "").strip()
    return tuple(p.strip().lower() for p in raw.split(",") if p.strip())


def _primary_platform() -> Optional[str]:
    plats = requested_platforms()
    return plats[0] if plats else None


def devices(backend: Optional[str] = None) -> list:
    """``jax.devices()`` behind the gate.

    With a platform restriction in force the query names that platform
    explicitly, so only its backend is ever initialized.  Backend init
    is the package's canonical non-cooperative hang class — with
    ``KAMINPAR_TPU_HARD_DEADLINE_S`` set the init runs under an armed
    watchdog stage (resilience/supervisor.py): the hang is recorded
    with its ceiling, the liveness heartbeat stalls so external
    supervisors can act, and a ``StageHang`` is async-delivered the
    moment the blocked call returns to the interpreter."""
    ensure_platform_env()
    import jax

    backend = backend or _primary_platform()
    with _backend_init_guard():
        return jax.devices(backend) if backend else jax.devices()


def local_devices(backend: Optional[str] = None) -> list:
    """``jax.local_devices()`` behind the gate (see devices())."""
    ensure_platform_env()
    import jax

    backend = backend or _primary_platform()
    return (
        jax.local_devices(backend=backend) if backend
        else jax.local_devices()
    )


def device_count() -> int:
    return len(devices())


def default_backend() -> str:
    """The default platform name.

    When the environment already pins the platform this answers without
    touching jax at all — no plug-in discovery."""
    plat = _primary_platform()
    if plat:
        return plat
    import jax

    return jax.default_backend()


def configure_compile_cache() -> str:
    """Point jax's persistent compilation cache at a stable directory
    and return it.  Every entry point that reaches the facade calls this
    before its first compile; it is the only place in the tree that
    assigns the cache directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the operator has placed
    the cache: jax reads the variable itself and nothing is assigned
    here.  Otherwise the directory is ``.jax_cache`` at the checkout's
    root, found from this package's own ``__file__`` — never from the
    working directory, a temporary name, a pid or the time, because the
    path is part of what a second process must find again."""
    import jax

    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        # keep every executable, not only those that took jax's default
        # of 1 s to compile: on the v5e 114 of the medium bench graph's
        # 139 programs compile in under a second each, and a second
        # process that recompiles them spends 28 s doing so where
        # loading them takes 16 s (my chip run, PR 21); they add 4 MB
        # to a 128 MB cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if placed:
        return placed
    here = os.path.dirname(os.path.abspath(__file__))
    cache_dir = os.path.join(
        os.path.dirname(os.path.dirname(here)), ".jax_cache"  # .gitignore
    )
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def process_index() -> int:
    """``jax.process_index()``, degrading to 0 without a live backend."""
    ensure_platform_env()
    try:
        import jax

        return int(jax.process_index())
    except Exception:
        return 0


def process_count() -> int:
    """``jax.process_count()``, degrading to 1 without a live backend."""
    ensure_platform_env()
    try:
        import jax

        return int(jax.process_count())
    except Exception:
        return 1
