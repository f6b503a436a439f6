"""Unified telemetry layer: metrics, tracing, compile watchdog.

  * :mod:`repro.obs.metrics` — labeled counters/gauges/histograms in a
    registry (process-global default, disabled until opted in, or an
    injected instance);
  * :mod:`repro.obs.trace` — span tracer on wall OR virtual clocks,
    Chrome-trace JSON + JSONL export; wall-clock spans also land in any
    ``jax.profiler`` trace;
  * :mod:`repro.obs.compile` — the ONE ``jax.monitoring``
    backend-compile listener: measurement context, enforcing watchdog,
    pytest fixture;
  * :mod:`repro.obs.meta` — benchmark run fingerprints for
    ``bench_compare``'s cross-backend refusal.

Quick start (everything off by default, zero overhead until enabled)::

    from repro import obs
    reg, tracer = obs.enable()          # turn the process defaults on
    ... run a round / an async run / a serve simulation ...
    reg.dump()                          # metrics as one JSON dict
    tracer.export_chrome("trace.json")  # load in chrome://tracing
"""
from repro.obs.compile import (CompileBudgetExceeded, CompileWatchdog,
                               compile_count, count_compiles)
from repro.obs.meta import run_meta
from repro.obs.metrics import (MetricsRegistry, default_registry,
                               get_registry, set_default_registry)
from repro.obs.trace import (Tracer, default_tracer, get_tracer,
                             set_default_tracer)


def enable() -> tuple[MetricsRegistry, Tracer]:
    """Switch the process-global registry AND tracer on; returns both."""
    reg, tracer = default_registry(), default_tracer()
    reg.enabled = True
    tracer.enabled = True
    return reg, tracer


def disable() -> None:
    default_registry().enabled = False
    default_tracer().enabled = False


__all__ = [
    "CompileBudgetExceeded", "CompileWatchdog", "MetricsRegistry",
    "Tracer", "compile_count", "count_compiles", "default_registry",
    "default_tracer", "disable", "enable", "get_registry", "get_tracer",
    "run_meta", "set_default_registry", "set_default_tracer",
]
