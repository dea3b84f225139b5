"""Device-level profiling: XLA cost analysis, compile accounting, HBM.

Answers *why* a step is slow, which the span tracer alone cannot:

  * **FLOPs / bytes per call** — each profiled jitted function's XLA
    ``cost_analysis()`` is captured at first compile (the AOT
    ``lower().compile()`` path, so the numbers come from the exact
    executable that runs);
  * **roofline attribution** — measured step wall time combines with the
    static FLOP count into achieved FLOP/s and a utilization-of-peak
    gauge (peak from a device-kind table; override with
    :func:`set_peak_flops` when you know your part's number);
  * **compile accounting** — compiles count, cumulative compile seconds,
    and recompile-CAUSE attribution: every compile is keyed by the
    abstract (shape, dtype) signature of its args, so a recompile names
    which argument's signature changed (the classic silent thief: a
    ragged batch recompiling every step);
  * **live-buffer HBM gauge** — :func:`sample_live_buffers` sums
    ``jax.live_arrays()`` sizes (current + peak), sampled per step by the
    trainer and per iteration by the GBDT engine.

Off by default, independent of the span tracer's switch:
``profiler.enable()`` (which also enables telemetry — the gauges live in
the shared registry) or ``TpuLearner.setProfile(True)``. A disabled :class:`ProfiledFunction` call is one
attribute check + delegation to the plain jitted function.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

from .registry import REGISTRY

_m_compiles = REGISTRY.counter(
    "mmlspark_profiler_compiles",
    "XLA compiles of profiled functions, by function tag and cause "
    "(first | shape_change | dtype_change)", labels=("fn", "cause"))
_m_compile_seconds = REGISTRY.counter(
    "mmlspark_profiler_compile_seconds",
    "cumulative wall seconds spent in XLA compilation of profiled "
    "functions", labels=("fn",))
_m_flops = REGISTRY.gauge(
    "mmlspark_profiler_flops_per_call",
    "XLA cost-analysis FLOPs of one call of the profiled function",
    labels=("fn",))
_m_bytes = REGISTRY.gauge(
    "mmlspark_profiler_bytes_per_call",
    "XLA cost-analysis bytes accessed by one call", labels=("fn",))
_m_achieved = REGISTRY.gauge(
    "mmlspark_profiler_achieved_flops",
    "achieved FLOP/s of the last profiled call (cost-analysis FLOPs / "
    "measured wall time)", labels=("fn",))
_m_roofline = REGISTRY.gauge(
    "mmlspark_profiler_roofline_utilization",
    "achieved FLOP/s as a fraction of the device peak (see "
    "set_peak_flops)", labels=("fn",))
_m_live_bytes = REGISTRY.gauge(
    "mmlspark_profiler_live_buffer_bytes",
    "bytes held by live jax arrays at the last sample")
_m_live_peak = REGISTRY.gauge(
    "mmlspark_profiler_live_buffer_peak_bytes",
    "high-water mark of live jax array bytes across samples")


class _PState:
    __slots__ = ("enabled",)

    def __init__(self):
        self.enabled = False


_pstate = _PState()
_lock = threading.Lock()
_live_peak = 0.0
_peak_flops_override: Optional[float] = None
_functions: dict = {}      # tag -> ProfiledFunction (for report())

#: bf16 peak FLOP/s by TPU ``device_kind`` prefix (public spec numbers).
#: libtpu 0.0.34 reports a v5e chip as "TPU v5 lite". A kind that is not
#: here is an error on an accelerator backend, not a default.
_PEAK_BY_KIND = {
    "TPU v2": 45e12, "TPU v3": 123e12, "TPU v4": 275e12,
    "TPU v5 lite": 197e12, "TPU v5e": 197e12, "TPU v5p": 459e12,
    "TPU v6e": 918e12, "TPU v6 lite": 918e12,
}


def enabled() -> bool:
    return _pstate.enabled


def enable():
    """Arm profiling (and telemetry — the profiler reports through the
    shared registry and tracer)."""
    from . import enable as telemetry_enable
    telemetry_enable()
    _pstate.enabled = True


def disable():
    _pstate.enabled = False


def set_peak_flops(value: Optional[float]):
    """Pin the roofline peak (FLOP/s) instead of the device-kind table."""
    global _peak_flops_override
    _peak_flops_override = value


def peak_flops() -> Optional[float]:
    """Device peak FLOP/s for the roofline denominator: the pinned
    override, else the device-kind table times the device count. None on
    the cpu backend (no peak is claimed for it, so no utilization is
    reported); an accelerator of unknown kind raises."""
    if _peak_flops_override:
        return _peak_flops_override
    import jax
    dev = jax.devices()[0]
    for prefix, peak in _PEAK_BY_KIND.items():
        if dev.device_kind.startswith(prefix):
            return peak * jax.device_count()
    if dev.platform == "cpu":
        return None
    raise RuntimeError(
        f"no peak FLOP/s known for device kind {dev.device_kind!r} "
        f"(platform {dev.platform!r}); add it to _PEAK_BY_KIND or call "
        f"set_peak_flops()")


def sample_live_buffers() -> float:
    """Sum live ``jax.Array`` bytes into the HBM gauges; returns the
    total (0.0 when profiling is off — the sample walks every live
    array, far too costly for the always-on path)."""
    global _live_peak
    if not _pstate.enabled:
        return 0.0
    import jax
    try:
        total = float(sum(a.nbytes for a in jax.live_arrays()))
    except Exception:
        return 0.0
    _m_live_bytes.set(total)
    with _lock:
        if total > _live_peak:
            _live_peak = total
    _m_live_peak.set(max(_live_peak, total))
    return total


def live_buffer_peak() -> float:
    return _live_peak


def _abstract_sig(args) -> tuple:
    """The (shape, dtype) signature jit keys its cache on, observed
    host-side over the flattened arg pytree."""
    import jax
    leaves, _ = jax.tree_util.tree_flatten(args)
    out = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        if shape is None:
            out.append(("py", repr(type(leaf).__name__)))
        else:
            out.append((tuple(shape), str(getattr(leaf, "dtype", ""))))
    return tuple(out)


def _diff_cause(prev: Optional[tuple], sig: tuple) -> str:
    if prev is None:
        return "first"
    for a, b in zip(prev, sig):
        if a != b:
            return "dtype_change" if a[0] == b[0] else "shape_change"
    return "shape_change"   # arity changed


def _extract_cost(compiled) -> dict:
    """Normalize ``compiled.cost_analysis()`` across jax versions (dict,
    list-of-dict, or None) into {"flops": float, "bytes": float}."""
    flops = bytes_ = 0.0
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if ca:
            flops = float(ca.get("flops", 0.0) or 0.0)
            bytes_ = float(ca.get("bytes accessed", 0.0) or 0.0)
    except Exception:
        pass
    return {"flops": flops, "bytes": bytes_}


class ProfiledFunction:
    """A jitted function observed through the profiler.

    Disabled (default): one flag check, then the plain jitted call —
    jit's own cache, async dispatch untouched. Enabled: calls route
    through the AOT path (``fn.lower(*args).compile()``) keyed by the
    abstract arg signature, so first-compile cost analysis, compile wall
    time, and recompile causes are all observed; each call is then timed
    to completion (``block_until_ready`` — profiling is an opt-in sync
    point, exactly like span ``sync=``).

    ``aot=True`` pins the AOT lower/compile cache on even while profiling
    is off (no per-call timing or ``block_until_ready`` then — async
    dispatch is untouched): the serving engine's warm-start story rides
    this cache — every shape bucket is compiled ahead of time
    (:meth:`aot_compile`), serialized executables from a bundle are
    seeded back in (:meth:`preload`), and compile counts/causes keep
    flowing to the recompile counters so "zero compiles on live traffic"
    is an assertable metric."""

    def __init__(self, fn, tag: str, aot: bool = False):
        self._fn = fn
        self.tag = tag
        self.aot = bool(aot)
        self._cache: dict = {}     # sig -> (compiled, cost)
        self._last_sig: Optional[tuple] = None
        self.compiles = 0
        self.compile_seconds = 0.0
        self.calls = 0
        self.last_call_seconds = 0.0
        self.cost = {"flops": 0.0, "bytes": 0.0}
        self.causes: dict[str, int] = {}
        with _lock:
            _functions[tag] = self

    def _compile(self, args, sig):
        from . import trace
        cause = _diff_cause(self._last_sig, sig)
        t0 = time.perf_counter()
        with trace.span("fit/compile", fn=self.tag, cause=cause):
            lowered = self._fn.lower(*args)
            compiled = lowered.compile()
        dt = time.perf_counter() - t0
        cost = _extract_cost(compiled)
        self.compiles += 1
        self.compile_seconds += dt
        self.causes[cause] = self.causes.get(cause, 0) + 1
        self.cost = cost
        _m_compiles.labels(fn=self.tag, cause=cause).inc()
        _m_compile_seconds.labels(fn=self.tag).inc(dt)
        _m_flops.labels(fn=self.tag).set(cost["flops"])
        _m_bytes.labels(fn=self.tag).set(cost["bytes"])
        return compiled, cost

    def is_cached(self, *args) -> bool:
        """Would a call with these args hit the AOT executable cache?
        (The serving engine's cache hit/miss accounting — a miss on live
        traffic is a cold compile somebody's request pays for.)"""
        return _abstract_sig(args) in self._cache

    def aot_compile(self, *args):
        """Compile (and cache) the executable for ``args``' abstract
        signature WITHOUT running it — args may be concrete arrays or
        ``jax.ShapeDtypeStruct``s. The warm-up entry point: serving
        buckets compile here at startup / bundle-build time, so no live
        request ever pays the compile. Returns the compiled executable
        (what :mod:`io/serving/bundle` serializes)."""
        sig = _abstract_sig(args)
        entry = self._cache.get(sig)
        if entry is None:
            entry = self._cache[sig] = self._compile(args, sig)
            self._last_sig = sig
        return entry[0]

    def preload(self, args, compiled) -> tuple:
        """Seed the AOT cache with a deserialized executable for
        ``args``' signature (no compile, no counter bump — the whole
        point of a warm start). Returns the cache signature."""
        sig = _abstract_sig(args)
        self._cache[sig] = (compiled, _extract_cost(compiled))
        self._last_sig = sig
        return sig

    def __call__(self, *args):
        if not _pstate.enabled:
            if not self.aot:
                return self._fn(*args)
            # AOT-pinned mode: executable-cache dispatch without the
            # profiler's sync point — async dispatch stays intact
            sig = _abstract_sig(args)
            entry = self._cache.get(sig)
            if entry is None:
                entry = self._cache[sig] = self._compile(args, sig)
                self._last_sig = sig
            self.calls += 1
            return entry[0](*args)
        import jax
        sig = _abstract_sig(args)
        entry = self._cache.get(sig)
        if entry is None:
            entry = self._cache[sig] = self._compile(args, sig)
        self._last_sig = sig
        compiled, cost = entry
        t0 = time.perf_counter()
        out = compiled(*args)
        jax.block_until_ready(out)
        dt = max(time.perf_counter() - t0, 1e-9)
        self.calls += 1
        self.last_call_seconds = dt
        if cost["flops"]:
            achieved = cost["flops"] / dt
            _m_achieved.labels(fn=self.tag).set(achieved)
            peak = peak_flops()
            if peak:
                _m_roofline.labels(fn=self.tag).set(achieved / peak)
        sample_live_buffers()
        return out


def wrap(fn, tag: str, aot: bool = False) -> ProfiledFunction:
    """Wrap a jitted function for profiling (idempotent per tag: wrapping
    replaces the report slot, not accumulates). ``aot=True`` keeps the
    executable cache live even while profiling is off (serving warm
    starts)."""
    if isinstance(fn, ProfiledFunction):
        return fn
    return ProfiledFunction(fn, tag, aot=aot)


def report() -> dict:
    """JSON-able profile summary — what ``docs/observability.md``
    documents."""
    peak = peak_flops()
    fns = {}
    with _lock:
        items = list(_functions.items())
    for tag, pf in items:
        if not pf.compiles and not pf.calls:
            continue
        achieved = (pf.cost["flops"] / pf.last_call_seconds
                    if pf.cost["flops"] and pf.last_call_seconds else 0.0)
        fns[tag] = {
            "flops_per_call": pf.cost["flops"],
            "bytes_per_call": pf.cost["bytes"],
            "compiles": pf.compiles,
            "compile_seconds": round(pf.compile_seconds, 4),
            "recompile_causes": dict(pf.causes),
            "calls": pf.calls,
            "last_call_seconds": round(pf.last_call_seconds, 6),
            "achieved_flops_per_sec": achieved,
            "roofline_utilization": (achieved / peak if peak else None),
        }
    return {"functions": fns, "peak_flops": peak,
            "live_buffer_bytes": _m_live_bytes.value,
            "live_buffer_peak_bytes": max(_live_peak,
                                          _m_live_peak.value)}


def reset():
    """Forget profiled functions + peaks (tests)."""
    global _live_peak
    with _lock:
        _functions.clear()
        _live_peak = 0.0
