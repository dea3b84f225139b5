"""Runtime telemetry: metrics registry, span tracing, exposition.

The observability backbone (reference: the MMLSpark ``core/metrics`` layer,
PAPER.md §1) every subsystem reports through — trainer step timing, GBDT
iteration breakdowns, dataplane transfer volume, serving fleet latency.

Usage::

    from mmlspark_tpu import telemetry
    _steps = telemetry.registry.counter("mmlspark_trainer_steps_total")
    ...
    _steps.inc()
    with telemetry.trace.span("fit/step", step=i):
        ...

Off by default: a disabled metric mutator is one attribute lookup + return,
a disabled span is a shared no-op context manager. Enable globally with the
``MMLSPARK_TPU_TELEMETRY=1`` environment switch (read via
``core.env.telemetry_enabled`` at import) or ``telemetry.enable()`` at
runtime. ``MMLSPARK_TPU_TRACE=/path/file.jsonl`` additionally exports the
span buffer as Chrome-trace JSON-lines at interpreter exit.

Scraping: the HTTP serving layer (io/http) exposes this process's registry
at ``GET /metrics`` in Prometheus text format; ``snapshot()`` returns the
JSON form bench tooling embeds next to its metric lines.
"""

from __future__ import annotations

from .registry import (DEFAULT_TIME_BUCKETS, REGISTRY, Counter, Gauge,
                       Histogram, MetricsRegistry, pow2_buckets, _state)
from .tracer import TRACER, Tracer, merge_traces
from . import context
from . import ledger
from . import profiler
from . import slo
from .flight import FLIGHT
from .timeseries import SAMPLER, TimeSeriesSampler

#: process-global singletons — the module-level API
registry = REGISTRY
trace = TRACER
flight = FLIGHT
timeseries = SAMPLER

__all__ = ["registry", "trace", "enabled", "enable", "disable",
           "snapshot", "prometheus_text", "warn_once", "merge_traces",
           "context", "ledger", "profiler", "flight", "timeseries", "slo",
           "federation",
           "Counter", "Gauge", "Histogram", "MetricsRegistry", "Tracer",
           "TimeSeriesSampler",
           "DEFAULT_TIME_BUCKETS", "pow2_buckets"]


def __getattr__(name):
    # lazy: federation pulls in resilience.policy (retry/breaker), which
    # imports this package — a deferred submodule import instead of a
    # cycle at package init
    if name == "federation":
        import importlib
        return importlib.import_module(".federation", __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def enabled() -> bool:
    return _state.enabled


def enable():
    if not _state.enabled:
        _state.enabled = True
        # the ring's clock against the Unix clock, from the first moment a
        # span can be recorded (tracer.py, "Two clocks, two routes")
        trace.anchor()


def disable():
    _state.enabled = False


def snapshot() -> dict:
    return registry.snapshot()


def prometheus_text() -> str:
    return registry.prometheus_text()


_warned_keys: set = set()
_warnings = registry.counter(
    "mmlspark_warnings_total",
    "one-time-logged warning occurrences by key", labels=("key",))


def warn_once(logger, key: str, msg: str, *args):
    """Log ``msg`` at WARNING once per ``key`` per process; bump the
    ``mmlspark_warnings_total{key=...}`` counter on EVERY occurrence (the
    log dedupes, the metric keeps counting — silent-after-first events
    stay visible on a dashboard)."""
    _warnings.labels(key=key).inc()
    if key not in _warned_keys:
        _warned_keys.add(key)
        logger.warning(msg, *args)


def _init_from_env():
    from ..core.env import (flight_path, telemetry_enabled,
                            telemetry_trace_path, timeseries_interval)
    if telemetry_enabled():
        enable()
    ts = timeseries_interval()
    if ts is not None:
        # arming the sampler also enables telemetry (a sampler over a
        # disabled registry records nothing)
        SAMPLER.start(interval=ts)
    path = telemetry_trace_path()
    if path:
        import atexit
        import os
        # "{pid}" templating: fleet worker processes inherit the same
        # env, so each needs its own export file to merge_traces later
        path = path.replace("{pid}", str(os.getpid()))
        atexit.register(lambda: trace.export_chrome_trace(path))
    fpath = flight_path()
    if fpath is not None:
        flight.enable(fpath or None)


_init_from_env()
