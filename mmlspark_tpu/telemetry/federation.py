"""Fleet metric federation: scrape worker time series, merge, evaluate.

PR 13 left the last observability gap in writing: for subprocess fleets
the driver's SLO engine evaluated only driver-visible series, so the
latencies the workers actually serve — the production signal (PAPERS.md,
arxiv 2605.25645) — never reached the burn verdicts, the autoscaler, or
``should_shed()``. This module closes it the way the data plane already
aggregates per-shard partials (``parallel/dataplane.py`` merge plans):

  * :class:`FleetScraper` periodically pulls every worker's control-port
    ``GET /timeseries`` (mmlspark-timeseries/v1, exposed since PR 7)
    through the shared :class:`~mmlspark_tpu.resilience.policy
    .RetryPolicy` + a per-worker
    :class:`~mmlspark_tpu.resilience.policy.CircuitBreaker`
    (chaos site ``federation.scrape``), and
  * folds them into a :class:`FederatedSampler` — the same
    ``keys`` / ``window_delta`` / ``window_points`` / ``value_at`` read
    surface as :class:`~.timeseries.TimeSeriesSampler`, so an unchanged
    :class:`~.slo.SLOEngine` evaluates fleet-wide series.

Merge rules per metric kind (chaos site ``federation.merge``):

* **cumulative** series (counters, histogram ``_count``/``_sum``/
  ``_bucket``) SUM across workers with monotonic-reset absorption: a
  restarted worker's counter drops toward 0, so the pre-restart plateau
  is folded into that worker's base offset — the merged series plateaus,
  it never goes negative (the fleet twin of the single-process
  ``timeseries/reset`` clamp);
* **histograms** therefore merge bucket-wise by ``le`` boundary — a
  window delta over merged buckets equals the single-process histogram
  on identical traffic;
* **gauges** aggregate per a declared policy: ``sum`` by default
  (additive levels: queue depth, inflight), ``max`` / ``last`` for the
  exceptions declared in :data:`GAUGE_POLICIES` (graftlint's
  ``metric-aggregation`` rule keeps that table and the metric
  catalogue's Aggregation column in lockstep, both directions).

Staleness: a worker whose scrape keeps failing is **stale** after
``staleness`` seconds. Its cumulative contribution stays frozen in the
sums (counted events don't un-happen) but it is excluded from gauge
merges, skew attribution, and the ``fresh`` count — SLO evaluation
degrades to the surviving workers instead of erroring. Every merged
series also keeps a ``worker="<id>"`` label child, so per-worker burn
stays inspectable from the driver (``GET /fleet/metrics``,
``GET /timeseries?scope=fleet``).

Per-worker latency attribution: the scraper feeds each fresh worker's
rolling request p99 (from its bucket deltas) to a
:class:`~.slo.StepTimeAnomalyDetector` — the same rolling-MAD shape the
trainer uses for stragglers — and emits advisory ``serving/skew``
instants + metrics when one worker runs anomalously slow while the
fleet-wide objective still looks healthy.
"""

from __future__ import annotations

import collections
import json
import threading
import time
import urllib.request
from typing import Optional

from ..resilience import faults
from ..resilience.policy import CircuitBreaker, RetryPolicy
from .registry import REGISTRY
from .slo import SLOEngine, StepTimeAnomalyDetector, _key_labels
from .timeseries import (SAMPLER, TimeSeriesSampler, is_cumulative,
                         percentile_from_buckets)

#: fleet aggregation policy for GAUGE families whose levels are NOT
#: additive across workers (everything absent here sums). Keys are
#: exposition names; values are ``max`` (worst-of-fleet) or ``last``
#: (driver-authoritative single writer — summing N identical copies
#: would overstate it N-fold). graftlint's ``metric-aggregation``
#: consistency rule checks this table against the metric catalogue's
#: Aggregation column in BOTH directions.
GAUGE_POLICIES = {
    "mmlspark_slo_state": "max",
    "mmlspark_slo_burn_rate": "max",
    "mmlspark_autoscale_state": "last",
    "mmlspark_autoscale_desired_workers": "last",
    "mmlspark_autoscale_observed_workers": "last",
    "mmlspark_autoscale_load_rows_per_worker": "last",
    "mmlspark_fleet_workers_alive": "last",
    "mmlspark_fleet_uncommitted_rows": "last",
    "mmlspark_federation_fresh_workers": "last",
    "mmlspark_federation_stale_workers": "last",
    "mmlspark_federation_skew_workers": "last",
    "mmlspark_rendezvous_generation": "max",
    "mmlspark_lease_term": "max",
    "mmlspark_elastic_hosts_alive": "last",
    "mmlspark_trainer_loss_scale": "last",
    "mmlspark_moe_expert_tokens_max": "max",
    "mmlspark_moe_tile_rows": "max",
    "mmlspark_moe_floor_tiles": "max",
    "mmlspark_breaker_state": "max",
    "mmlspark_serving_pad_waste": "max",
    "mmlspark_graftlint_findings": "last",
    "mmlspark_pipeline_segments": "last",
    "mmlspark_profiler_flops_per_call": "max",
    "mmlspark_profiler_bytes_per_call": "max",
    "mmlspark_profiler_achieved_flops": "max",
    "mmlspark_profiler_roofline_utilization": "max",
    "mmlspark_tune_rung_metric": "last",
    "mmlspark_tune_trial_rung": "max",
    "mmlspark_tune_trial_progress": "max",
    "mmlspark_tune_active_trials": "last",
}

_m_scrapes = REGISTRY.counter(
    "mmlspark_federation_scrapes",
    "worker time-series scrapes by outcome", labels=("outcome",))
_m_merge_errors = REGISTRY.counter(
    "mmlspark_federation_merge_errors",
    "merge rounds skipped by an error (the next round re-merges)")
_m_resets = REGISTRY.counter(
    "mmlspark_federation_counter_resets",
    "monotonic resets absorbed from restarted workers' cumulative series")
_m_fresh = REGISTRY.gauge(
    "mmlspark_federation_fresh_workers",
    "workers whose last scrape is inside the staleness window")
_m_stale = REGISTRY.gauge(
    "mmlspark_federation_stale_workers",
    "workers excluded from gauge merges after staleness-window expiry "
    "(their cumulative contribution stays frozen in the sums)")
_m_skew = REGISTRY.gauge(
    "mmlspark_federation_skew_workers",
    "workers currently flagged by the per-worker latency-skew detector")
_m_skew_flags = REGISTRY.counter(
    "mmlspark_federation_skew_flagged",
    "transitions into the latency-skew verdict, by worker",
    labels=("worker",))


def _with_worker(key: str, worker: str) -> str:
    """Re-key a series with a ``worker=`` label child (appended after
    the existing labels, exposition-rendered)."""
    base, brace, rest = key.partition("{")
    if not brace:
        return f'{base}{{worker="{worker}"}}'
    return f'{base}{{{rest[:-1]},worker="{worker}"}}'


class _WorkerSeries:
    """One worker's per-key cumulative state: last raw value + the base
    offset absorbing pre-restart plateaus."""

    __slots__ = ("last", "base")

    def __init__(self):
        self.last: dict[str, float] = {}   # key -> last raw scraped value
        self.base: dict[str, float] = {}   # key -> absorbed reset offset


class FederatedSampler(TimeSeriesSampler):
    """Merged fleet-wide rings behind the TimeSeriesSampler read surface.

    Ingest side: :meth:`ingest` stores one worker's scraped snapshot;
    :meth:`merge` folds the latest values of every fresh worker (plus,
    optionally, the driver's own local sampler as pseudo-worker
    ``driver``) into the inherited rings — so ``window_delta`` /
    ``window_points`` / ``value_at`` / ``snapshot`` are literally the
    parent's ring algorithms over fleet-wide series. ``tick`` is
    disabled: points enter through merge rounds, never a registry walk.
    """

    def __init__(self, interval: float = 1.0, capacity: int = 600,
                 staleness: Optional[float] = None,
                 local: Optional[TimeSeriesSampler] = None,
                 gauge_policies: Optional[dict] = None):
        super().__init__(interval=interval, capacity=capacity)
        self.staleness = (float(staleness) if staleness is not None
                          else 5.0 * float(interval))
        self.local = local
        self.gauge_policies = dict(gauge_policies if gauge_policies
                                   is not None else GAUGE_POLICIES)
        self._workers: dict[str, _WorkerSeries] = {}    # guarded-by: _lock
        self._values: dict[str, dict[str, float]] = {}  # guarded-by: _lock
        self._last_seen: dict[str, float] = {}          # guarded-by: _lock
        self._first_merge = True                        # guarded-by: _lock
        # per-worker exemplar side channel from ingested snapshots
        # (bucket series key -> {"trace_id", "value"}); merge folds them
        # into the inherited _exemplars map with worker identity intact
        self._worker_exemplars: dict[str, dict] = {}    # guarded-by: _lock

    def tick(self, now: Optional[float] = None) -> int:
        raise NotImplementedError(
            "FederatedSampler is fed by FleetScraper.ingest/merge, "
            "not by registry ticks")

    # ------------------------------------------------------------- ingest
    def ingest(self, worker: str, snapshot: dict,
               now: Optional[float] = None) -> int:
        """Store one worker's mmlspark-timeseries/v1 snapshot: the LAST
        point of each series is its current cumulative value / gauge
        level. Monotonic resets (a restarted incarnation's counter below
        its predecessor) fold the old value into the worker's base
        offset. Returns the number of series ingested."""
        t = time.time() if now is None else float(now)
        series = snapshot.get("series", {})
        values = {key: float(pts[-1][1])
                  for key, pts in series.items() if pts}
        resets = 0
        with self._lock:
            ws = self._workers.get(worker)
            if ws is None:
                ws = self._workers[worker] = _WorkerSeries()
            for key, v in values.items():
                if is_cumulative(key):
                    prev = ws.last.get(key)
                    if prev is not None and v < prev:
                        ws.base[key] = ws.base.get(key, 0.0) + prev
                        resets += 1
                    ws.last[key] = v
            # update, never replace: a series absent from one snapshot
            # (ring cleared, partial scrape) keeps its last contribution
            # frozen instead of stepping the merged sum down
            self._values.setdefault(worker, {}).update(values)
            self._last_seen[worker] = t
            exemplars = snapshot.get("exemplars")
            if exemplars:
                self._worker_exemplars.setdefault(worker, {}).update(
                    {k: dict(ex) for k, ex in exemplars.items()})
        if resets:
            _m_resets.inc(resets)
            from . import flight, trace
            trace.instant("federation/reset", worker=worker, series=resets)
            flight.note("federation/reset", worker=worker, series=resets)
        return len(values)

    def fresh_workers(self, now: Optional[float] = None) -> list:
        """Workers whose last successful scrape is inside the staleness
        window (sorted)."""
        t = time.time() if now is None else float(now)
        with self._lock:
            return sorted(w for w, seen in self._last_seen.items()
                          if t - seen <= self.staleness)

    def stale_workers(self, now: Optional[float] = None) -> list:
        t = time.time() if now is None else float(now)
        with self._lock:
            return sorted(w for w, seen in self._last_seen.items()
                          if t - seen > self.staleness)

    def forget_worker(self, worker: str, absorb: bool = True):
        """Drop one worker's scrape state (retired slot). ``absorb=True``
        keeps its cumulative contribution by folding it into a synthetic
        retired tally under the same mechanism a reset uses — the merged
        counters plateau instead of stepping down."""
        with self._lock:
            ws = self._workers.get(worker)
            if ws is not None and absorb:
                # re-file the contribution under a parked incarnation
                # whose values never change again
                for key in list(ws.last):
                    ws.base[key] = ws.base.get(key, 0.0) + ws.last.pop(key)
                self._values.pop(worker, None)
                self._last_seen.pop(worker, None)
            elif ws is not None:
                self._workers.pop(worker, None)
                self._values.pop(worker, None)
                self._last_seen.pop(worker, None)
            self._worker_exemplars.pop(worker, None)

    # -------------------------------------------------------------- merge
    def _merged_values(self, now: float) -> dict[str, float]:
        """One merged value per series key + per-worker children, from
        every worker's latest scrape (cumulative: frozen-stale workers
        stay in the sums; gauges: fresh workers only, per policy)."""
        with self._lock:
            workers = dict(self._workers)
            values = {w: dict(v) for w, v in self._values.items()}
            seen = dict(self._last_seen)
        fresh = {w for w, s in seen.items()
                 if now - s <= self.staleness}
        merged: dict[str, float] = {}
        gauge_acc: dict[str, list] = {}
        # union: a parked incarnation (forget_worker absorb) has bases but
        # no live values — it must still reach the parked-bases branch
        order = sorted(set(values) | set(workers))
        for w in order:
            ws = workers.get(w)
            for key, v in values.get(w, {}).items():
                if is_cumulative(key):
                    contrib = v + (ws.base.get(key, 0.0) if ws else 0.0)
                    merged[key] = merged.get(key, 0.0) + contrib
                    merged[_with_worker(key, w)] = contrib
                elif w in fresh:
                    gauge_acc.setdefault(key, []).append(v)
                    merged[_with_worker(key, w)] = v
            if ws:
                # parked incarnations (forget_worker absorb): bases with
                # no live value still belong in the sums
                for key, b in ws.base.items():
                    if key not in values.get(w, {}):
                        merged[key] = merged.get(key, 0.0) + b
                        merged[_with_worker(key, w)] = b
        for key, vals in gauge_acc.items():
            base, _labels = _key_labels(key)
            policy = self.gauge_policies.get(base, "sum")
            if policy == "max":
                merged[key] = max(vals)
            elif policy == "last":
                merged[key] = vals[-1]
            else:
                merged[key] = sum(vals)
        return merged

    def merge(self, now: Optional[float] = None) -> int:
        """One merge round: fold the latest per-worker values into the
        rings (chaos site ``federation.merge`` — an injected fault skips
        this round, counted; the next round re-merges everything).
        Returns the number of points appended."""
        t = time.time() if now is None else float(now)
        if self.local is not None:
            # the driver's own series ride the same merge as pseudo-worker
            # "driver" — objectives over driver-side counters (offset-log
            # goodput) keep evaluating alongside worker-side histograms
            try:
                self.ingest("driver", self.local.snapshot(), now=t)
            except Exception:
                pass
        try:
            faults.inject("federation.merge")
            merged = self._merged_values(t)
        except Exception:
            _m_merge_errors.inc()
            return 0
        appended = 0
        with self._lock:
            first = self._first_merge
            self._first_merge = False
            for key, v in merged.items():
                ring = self._rings.get(key)
                if ring is None:
                    ring = self._rings[key] = collections.deque(
                        maxlen=self.capacity)
                    if first:
                        self._seeded.add(key)
                elif ring[-1][1] == v:
                    continue    # carry-forward: unchanged values add no point
                ring.append((t, v))
                appended += 1
            # fold worker exemplars into the merged side channel: each
            # worker-child bucket series keeps its own exemplar, and the
            # fleet aggregate carries the exemplar WITH its worker
            # identity (sorted fold — last worker wins deterministically)
            for w in sorted(self._worker_exemplars):
                for key, ex in self._worker_exemplars[w].items():
                    self._exemplars[_with_worker(key, w)] = dict(ex)
                    agg = dict(ex)
                    agg.setdefault("worker", w)
                    self._exemplars[key] = agg
        _m_fresh.set(len(self.fresh_workers(t)))
        _m_stale.set(len(self.stale_workers(t)))
        return appended

    # ----------------------------------------------------------- exposure
    def prometheus_text(self, now: Optional[float] = None) -> str:
        """Aggregated exposition of the merged series' latest values —
        the ``GET /fleet/metrics`` payload (fleet-wide aggregates plus
        ``worker=`` children, one scrape shows both)."""
        lines = ["# mmlspark fleet federation: merged worker series "
                 "(aggregates + worker= children)"]
        with self._lock:
            for key in sorted(self._rings):
                ring = self._rings[key]
                if ring:
                    v = ring[-1][1]
                    line = f"{key} {v:g}"
                    ex = self._exemplars.get(key)
                    if ex is not None and ex.get("trace_id"):
                        # OpenMetrics exemplar: the tail-retained trace
                        # behind this bucket, with the worker that
                        # observed it (fetch via GET /debug/trace/<id>)
                        labs = [f'trace_id="{ex["trace_id"]}"']
                        if ex.get("worker"):
                            labs.append(f'worker="{ex["worker"]}"')
                        line += (" # {" + ",".join(labs) + "} "
                                 + f'{float(ex.get("value", v)):g}')
                    lines.append(line)
        return "\n".join(lines) + "\n"

    def worker_percentile(self, worker: str, hist: str, q: float,
                          window: float,
                          now: Optional[float] = None) -> Optional[float]:
        """One worker's latency quantile from its merged bucket children
        over ``window`` (None without data) — skew attribution's input."""
        t = time.time() if now is None else float(now)
        deltas: dict[str, float] = {}
        for key in self.keys():
            base, labels = _key_labels(key)
            if base != f"{hist}_bucket" or labels.get("worker") != worker:
                continue
            le = labels.get("le")
            if le is None:
                continue
            d = self.window_delta(key, window, t)
            if d:
                deltas[le] = deltas.get(le, 0.0) + d
        return percentile_from_buckets(deltas, q) if deltas else None


class FleetScraper:
    """Driver-side scrape loop over the worker fleet's ``/timeseries``.

    ``source`` is a :class:`~mmlspark_tpu.io.http.fleet
    .ProcessHTTPSource` (targets derive from its live workers each
    round, so reconciler spawns/retires are followed automatically);
    tests and the bench pass explicit ``targets`` —
    ``[(worker_id, url), ...]`` or a callable returning them. Each
    round-trip runs through the shared RetryPolicy and a per-worker
    CircuitBreaker (chaos site ``federation.scrape``): a flapping worker
    trips its breaker and is skipped — it goes stale, merges degrade to
    the survivors, and the breaker's half-open probe brings it back.

    ``slo`` (optional, with ``push_shed=True``) pushes the engine's
    fleet-burn shed verdict to every worker's control ``POST /shed``
    after each round, so worker-door 503s carry the burn-derived
    Retry-After even though the engine runs on the driver."""

    def __init__(self, source=None, targets=None, interval: float = 1.0,
                 timeout: float = 2.0, staleness: Optional[float] = None,
                 sampler: Optional[FederatedSampler] = None,
                 skew_hist: str = "mmlspark_http_request_seconds",
                 skew_window: Optional[float] = None,
                 skew: Optional[StepTimeAnomalyDetector] = None,
                 slo: Optional[SLOEngine] = None,
                 push_shed: bool = False):
        if (source is None) == (targets is None):
            raise ValueError("pass exactly one of source / targets")
        self.source = source
        self._targets = targets
        self.interval = float(interval)
        self.timeout = float(timeout)
        self.sampler = sampler if sampler is not None else FederatedSampler(
            interval=interval, staleness=staleness, local=SAMPLER)
        self.skew_hist = skew_hist
        self.skew_window = (float(skew_window) if skew_window is not None
                            else 30.0 * float(interval))
        # the trainer's rolling-MAD straggler shape over per-worker p99:
        # smaller window (p99 is already an aggregate) and a 2x floor —
        # advisory attribution, not an eviction verdict
        self.skew = skew if skew is not None else StepTimeAnomalyDetector(
            window=16, k=5.0, min_samples=4, min_ratio=2.0)
        self.slo = slo
        self.push_shed = bool(push_shed)
        # transient scrape blips retry in-line; a worker that keeps
        # failing trips its breaker and is skipped until half-open probes
        # find it answering again (it goes stale in the meantime)
        self._retry = RetryPolicy(name="federation.scrape",
                                  max_attempts=2, base_delay=0.02,
                                  max_delay=0.1)
        self.breaker = CircuitBreaker("federation.scrape",
                                      failure_threshold=3,
                                      reset_timeout=1.0)
        # scrape_once is public (deterministic tests drive it directly)
        # while _run calls it from the scraper thread, and healthz()
        # reads the round bookkeeping from request threads
        self._lock = threading.RLock()
        self._skewed: set[str] = set()                  # guarded-by: _lock
        self._last_shed: Optional[tuple] = None         # guarded-by: _lock
        self._rounds = 0                                # guarded-by: _lock
        self._errors: dict[str, str] = {}               # guarded-by: _lock
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------ targets
    def targets(self) -> list:
        """``[(worker_id, timeseries_url, shed_url|None), ...]`` for this
        round."""
        if self._targets is not None:
            t = self._targets() if callable(self._targets) else self._targets
            return [(str(w), url, None) for w, url in t]
        out = []
        for wi, w in enumerate(self.source.workers):
            if w.retired or not w.alive:
                continue
            ctrl = f"http://{w.host}:{w.control}"
            out.append((str(wi), f"{ctrl}/timeseries", f"{ctrl}/shed"))
        return out

    # ------------------------------------------------------------- scrape
    def _fetch(self, url: str) -> dict:
        faults.inject("federation.scrape")
        with urllib.request.urlopen(url, timeout=self.timeout) as r:
            return json.loads(r.read() or b"{}")

    def scrape_once(self, now: Optional[float] = None) -> dict:
        """One full round: scrape every target, merge, attribute skew,
        push the shed verdict. Returns ``{worker: ok_bool}``."""
        t = time.time() if now is None else float(now)
        results: dict[str, bool] = {}
        shed_urls: dict[str, str] = {}
        for wid, url, shed_url in self.targets():
            if shed_url:
                shed_urls[wid] = shed_url
            if not self.breaker.allow(wid):
                results[wid] = False
                _m_scrapes.labels(outcome="skipped").inc()
                continue        # circuit open: skip the doomed round-trip
            try:
                snap = self._retry.run(lambda _a, u=url: self._fetch(u))
                self.breaker.record(wid, ok=True)
                self.sampler.ingest(wid, snap, now=t)
                with self._lock:
                    self._errors.pop(wid, None)
                results[wid] = True
                _m_scrapes.labels(outcome="ok").inc()
            except Exception as e:
                self.breaker.record(wid, ok=False)
                with self._lock:
                    self._errors[wid] = str(e)
                results[wid] = False
                _m_scrapes.labels(outcome="error").inc()
        self.sampler.merge(now=t)
        with self._lock:
            self._rounds += 1
            self._attribute_skew(t)
        if self.push_shed and self.slo is not None:
            self._push_shed(shed_urls)
        return results

    # ---------------------------------------------------- skew attribution
    # requires-lock: _lock
    def _attribute_skew(self, now: float):
        fresh = set(self.sampler.fresh_workers(now))
        for wid in self.sampler.stale_workers(now):
            # a stale worker's window is noise the moment it stops
            # answering; keeping it would hold its flag forever
            self.skew.forget(wid)
            self._skewed.discard(wid)
        for wid in sorted(fresh):
            if wid == "driver":
                continue    # the driver serves no requests to attribute
            p = self.sampler.worker_percentile(
                wid, self.skew_hist, 0.99, self.skew_window, now=now)
            if p is not None:
                self.skew.observe(wid, p)
        flagged = self.skew.stragglers() & fresh
        _m_skew.set(len(flagged))
        if flagged != self._skewed:
            from . import flight, trace
            for wid in sorted(flagged - self._skewed):
                med = self.skew.host_medians()
                _m_skew_flags.labels(worker=wid).inc()
                trace.instant("serving/skew", worker=wid,
                              p99_s=med.get(wid))
                flight.note("serving/skew", worker=wid,
                            p99_s=med.get(wid),
                            fleet=
                            {w: round(v, 6) for w, v in med.items()})
            for wid in sorted(self._skewed - flagged):
                trace.instant("serving/skew", worker=wid, cleared=True)
            self._skewed = set(flagged)

    # ----------------------------------------------------------- shed push
    def _push_shed(self, shed_urls: dict):
        """Propagate the driver engine's fleet-burn verdict to the worker
        doors (state changes only — a steady verdict costs nothing)."""
        shed = self.slo.should_shed()
        retry_after = self.slo.retry_after() if shed else None
        state = (shed, retry_after)
        with self._lock:
            if state == self._last_shed:
                return
        payload = json.dumps({"shed": shed,
                              "retry_after": retry_after}).encode()
        delivered = True
        for wid, url in shed_urls.items():
            try:
                req = urllib.request.Request(
                    url, data=payload,
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=self.timeout):
                    pass
            except Exception:
                delivered = False   # retried next round: state not latched
        if delivered:
            with self._lock:
                self._last_shed = state

    # ------------------------------------------------------------- surface
    def healthz(self) -> dict:
        """The ``federation`` section of the fleet healthz doc."""
        now = time.time()
        fresh = self.sampler.fresh_workers(now)
        stale = self.sampler.stale_workers(now)
        with self._lock:
            return {"rounds": self._rounds,
                    "interval_s": self.interval,
                    "staleness_s": self.sampler.staleness,
                    "fresh_workers": fresh,
                    "stale_workers": stale,
                    "scrape_errors": dict(self._errors),
                    "breakers": self.breaker.snapshot(),
                    "skew": self.skew.report()}

    # ----------------------------------------------------------- lifecycle
    def start(self) -> "FleetScraper":
        if self._thread is not None and self._thread.is_alive():
            return self
        self._stop.clear()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="fleet-scraper")
        self._thread.start()
        return self

    def _run(self):
        while not self._stop.is_set():
            try:
                self.scrape_once()
            except Exception:   # a scrape bug must not kill the loop
                pass
            self._stop.wait(self.interval)

    def stop(self):
        self._stop.set()
        t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout=5)
        self._thread = None
