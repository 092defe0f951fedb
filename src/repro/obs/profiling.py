"""Compile-event counters (DESIGN.md §11).

Compile counters are *fed* from the subsystems' existing surfaces
(``xl.stream.compile_counts()``, the serving ``_JitCache`` stats) rather
than hooked into jax internals, so they stay exact and host-side.
``jax.profiler`` captures need no wrapper here: every ``obs.span`` is
marked in the profiler trace by name (``obs.trace``).
"""
from __future__ import annotations

from typing import Mapping, Optional

from repro.obs import _state
from repro.obs.metrics import MetricsRegistry, default_registry

__all__ = [
    "record_compile_counts",
]


def record_compile_counts(
    counts: Mapping[str, float],
    registry: Optional[MetricsRegistry] = None,
    prefix: str = "compile_cache_entries",
) -> None:
    """Mirror a subsystem's compile-cache surface (program -> #entries or
    hit/miss counts) into labeled gauges; a growing entry count between two
    samples is a recompile event."""
    if not _state.is_enabled():
        return
    reg = registry if registry is not None else default_registry()
    for program, n in counts.items():
        reg.gauge(prefix, program=str(program)).set(float(n))
