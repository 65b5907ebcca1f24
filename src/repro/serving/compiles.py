"""Process-wide count of JAX backend compiles, from JAX's monitoring
events. One pair of listeners, registered when this module is first
imported, counts every compile after it; a reader takes the difference
of two `snapshot()`s (``chip_smoke.py`` does, around its passes). A
persistent-cache hit also reports a backend-compile duration (the time to
fetch it), so ``compiles`` counts hits too and ``cache_hits`` says how
many of them there were."""
from __future__ import annotations

import jax

_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

_counts = {"compiles": 0, "compile_seconds": 0.0, "cache_hits": 0}


def _on_duration(event: str, secs: float, **_) -> None:
    if event == _COMPILE:
        _counts["compiles"] += 1
        _counts["compile_seconds"] += secs


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT:
        _counts["cache_hits"] += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def snapshot() -> dict:
    """The counts so far in this process: ``compiles``,
    ``compile_seconds`` and ``cache_hits``."""
    return dict(_counts)
