"""Perf observatory: time-series sampling over the metrics registry,
SLO burn-rate evaluation (+ the serving /healthz + shedding surface),
and rolling-MAD straggler detection."""

import json
import urllib.request

import numpy as np
import pytest

from mmlspark_tpu import telemetry
from mmlspark_tpu.telemetry.registry import MetricsRegistry
from mmlspark_tpu.telemetry.slo import (SLOEngine, SLOObjective,
                                        StepTimeAnomalyDetector)
from mmlspark_tpu.telemetry.timeseries import (TimeSeriesSampler,
                                               load_jsonl,
                                               percentile_from_buckets)


@pytest.fixture
def tel():
    """Enabled telemetry with clean state; restores disabled default."""
    telemetry.registry.reset()
    telemetry.trace.clear()
    telemetry.enable()
    yield telemetry
    telemetry.disable()
    telemetry.registry.reset()
    telemetry.trace.clear()


# ---------------------------------------------------- registry snapshot_delta

class TestSnapshotDelta:
    def test_changed_families_only(self, tel):
        reg = MetricsRegistry()
        a = reg.counter("t_sd_a", "a")
        b = reg.counter("t_sd_b", "b")
        a.inc()
        b.inc(2)
        changed, token = reg.snapshot_delta(None)
        assert {"t_sd_a", "t_sd_b"} <= set(changed)
        # quiet tick: nothing changed, nothing rebuilt
        changed2, token2 = reg.snapshot_delta(token)
        assert changed2 == {}
        assert token2 == token
        # one write -> exactly that family comes back
        a.inc(3)
        changed3, _ = reg.snapshot_delta(token2)
        assert set(changed3) == {"t_sd_a"}
        assert changed3["t_sd_a"]["series"][0]["value"] == 4

    def test_labeled_series_and_histograms(self, tel):
        reg = MetricsRegistry()
        c = reg.counter("t_sd_lab", "l", labels=("k",))
        h = reg.histogram("t_sd_h", "h", buckets=(1.0, 2.0))
        _, token = reg.snapshot_delta(None)
        c.labels(k="x").inc()
        h.observe(1.5)
        changed, _ = reg.snapshot_delta(token)
        assert set(changed) == {"t_sd_lab", "t_sd_h"}

    def test_reset_is_a_change(self, tel):
        reg = MetricsRegistry()
        c = reg.counter("t_sd_r", "r")
        c.inc(5)
        _, token = reg.snapshot_delta(None)
        reg.reset()
        changed, _ = reg.snapshot_delta(token)
        assert changed["t_sd_r"]["series"][0]["value"] == 0


# ------------------------------------------------------------- time series

class TestTimeSeries:
    def _sampler(self, capacity=600):
        reg = MetricsRegistry()
        return reg, TimeSeriesSampler(registry=reg, capacity=capacity)

    def test_exposition_keys(self, tel):
        reg, ts = self._sampler()
        reg.counter("t_ts_c", "c").inc()
        reg.gauge("t_ts_g", "g").set(7)
        reg.histogram("t_ts_h", "h", buckets=(1.0,)).observe(0.5)
        reg.counter("t_ts_l", "l", labels=("w",)).labels(w="0").inc()
        ts.tick(now=1.0)
        keys = set(ts.keys())
        assert "t_ts_c_total" in keys           # counter suffix
        assert "t_ts_g" in keys                 # gauge bare
        assert {"t_ts_h_count", "t_ts_h_sum"} <= keys
        assert 't_ts_h_bucket{le="1"}' in keys
        assert 't_ts_h_bucket{le="+Inf"}' in keys
        assert 't_ts_l_total{w="0"}' in keys    # labels render

    def test_ring_eviction(self, tel):
        reg, ts = self._sampler(capacity=3)
        c = reg.counter("t_ts_ring", "r")
        for i in range(5):
            c.inc()
            ts.tick(now=float(i))
        pts = ts.series("t_ts_ring_total")
        # oldest two dropped; survivors keep (t, cumulative) order
        assert pts == [(2.0, 3.0), (3.0, 4.0), (4.0, 5.0)]

    def test_quiet_series_not_reappended(self, tel):
        reg, ts = self._sampler()
        c = reg.counter("t_ts_q", "q")
        c.inc()
        ts.tick(now=1.0)
        ts.tick(now=2.0)    # no writes: no new point
        assert len(ts.series("t_ts_q_total")) == 1

    def test_window_delta_and_value_at(self, tel):
        reg, ts = self._sampler()
        c = reg.counter("t_ts_w", "w")
        for t, inc in ((0.0, 1), (10.0, 2), (20.0, 4)):
            c.inc(inc)
            ts.tick(now=t)
        key = "t_ts_w_total"
        assert ts.value_at(key, 15.0) == 3.0            # carry-forward
        assert ts.value_at(key, -1.0) is None
        assert ts.window_delta(key, 10.0, now=20.0) == 4.0
        assert ts.window_delta(key, 100.0, now=20.0) == 6.0  # partial
        assert ts.window_delta(key, 5.0, now=-5.0) is None

    def test_series_born_mid_sampling_baseline_is_zero(self, tel):
        """A labeled child minted by its first write (the first 500
        reply ever) must show its whole first burst in a window delta —
        its value before birth was 0 — while a series that predates the
        sampler keeps the earliest-point baseline (its pre-sampling
        history is unknown)."""
        reg, ts = self._sampler()
        c = reg.counter("t_ts_b", "b", labels=("code",))
        c.labels(code="200").inc()
        ts.tick(now=0.0)                 # seeds the 200 series
        c.labels(code="500").inc(4)      # born mid-sampling
        ts.tick(now=31.0)
        k200 = 't_ts_b_total{code="200"}'
        k500 = 't_ts_b_total{code="500"}'
        # seeded + window predating the first tick: earliest point
        # stands in (no phantom +1 burst at sampler startup)
        assert ts.window_delta(k200, 100.0, now=31.0) == 0.0
        # born mid-sampling: baseline 0, the burst is fully visible
        assert ts.window_delta(k500, 5.0, now=31.0) == 4.0

    def test_jsonl_round_trip(self, tel, tmp_path):
        reg, ts = self._sampler()
        c = reg.counter("t_ts_io", "io")
        g = reg.gauge("t_ts_io_g", "g")
        for t in (1.0, 2.0, 3.0):
            c.inc()
            g.set(t * 10)
            ts.tick(now=t)
        path = str(tmp_path / "ts.jsonl")
        n = ts.export_jsonl(path)
        assert n == len(ts.keys())
        loaded = load_jsonl(path)
        assert loaded["t_ts_io_total"] == [(1.0, 1.0), (2.0, 2.0),
                                           (3.0, 3.0)]
        assert loaded["t_ts_io_g"][-1] == (3.0, 30.0)

    def test_snapshot_schema(self, tel):
        reg, ts = self._sampler()
        reg.counter("t_ts_s", "s").inc()
        ts.tick(now=1.0)
        doc = ts.snapshot()
        assert doc["schema"] == "mmlspark-timeseries/v1"
        assert doc["series"]["t_ts_s_total"] == [[1.0, 1.0]]

    def test_percentile_from_buckets(self):
        # cumulative deltas: 90 at <=0.1, 99 at <=1.0, 100 total
        deltas = {"0.1": 90.0, "1.0": 99.0, "+Inf": 100.0}
        assert percentile_from_buckets(deltas, 0.5) == 0.1
        assert percentile_from_buckets(deltas, 0.99) == 1.0
        assert percentile_from_buckets(deltas, 1.0) == float("inf")
        assert percentile_from_buckets({}, 0.5) is None


# ------------------------------------------------------------ SLO objectives

class TestSLOEngine:
    def _world(self):
        reg = MetricsRegistry()
        ts = TimeSeriesSampler(registry=reg)
        eng = SLOEngine([{
            "name": "errors", "kind": "error_rate",
            "bad": "t_slo_bad_total",
            "total": "t_slo_requests_total",
            "target": 0.9,              # 10% error budget
            "windows": [10.0, 60.0],
        }], sampler=ts)
        reg.counter("t_slo_bad", "bad")
        total = reg.counter("t_slo_requests", "total")
        return reg, ts, eng, total

    def test_spec_validation(self):
        with pytest.raises(ValueError, match="unknown kind"):
            SLOObjective("x", "nope")
        with pytest.raises(ValueError, match="missing"):
            SLOObjective("x", "error_rate", bad="b", total="t")
        with pytest.raises(ValueError, match="windows"):
            SLOObjective("x", "latency", windows=(60, 60), hist="h",
                         threshold_s=0.1, target=0.99)
        with pytest.raises(ValueError, match="duplicate"):
            SLOEngine([
                {"name": "a", "kind": "step_time", "hist": "h",
                 "budget_s": 1.0},
                {"name": "a", "kind": "step_time", "hist": "h",
                 "budget_s": 2.0}])

    def test_burn_breach_and_recovery(self, tel):
        reg, ts, eng, total = self._world()
        bad = reg.counter("t_slo_bad", "bad")
        telemetry.flight.enable()
        try:
            # healthy traffic fills both windows
            for t in (0.0, 30.0, 60.0):
                total.inc(100)
                ts.tick(now=t)
            r = eng.evaluate(now=60.0)
            assert r["errors"]["state"] == "ok"
            # an error burst: 50% errors vs a 10% budget burns both the
            # fast (10s) and slow (60s) windows -> breach transition
            total.inc(100)
            bad.inc(50)
            ts.tick(now=65.0)
            r = eng.evaluate(now=65.0)
            assert r["errors"]["state"] == "breach"
            assert r["errors"]["burn_fast"] > 1.0
            assert r["errors"]["burn_slow"] > 1.0
            assert eng.breached() == {"errors"}
            # the transition surfaced as a trace instant + flight note
            names = [e.get("name") for e in telemetry.trace.events()]
            assert "slo/breach" in names
            kinds = [e for e in telemetry.flight.bundle()["events"]
                     if e.get("kind") == "note"
                     and e.get("name") == "slo/breach"]
            assert kinds
            # quiet recovery: the fast window clears first, then the slow
            for t in (120.0, 125.0, 130.0):
                total.inc(200)
                ts.tick(now=t)
            r = eng.evaluate(now=130.0)
            assert r["errors"]["state"] == "ok"
            assert eng.breached() == set()
            assert eng.breached_ever() == {"errors"}
            names = [e.get("name") for e in telemetry.trace.events()]
            assert "slo/recover" in names
        finally:
            telemetry.flight.disable()
            telemetry.flight.clear()

    def test_one_window_burning_is_not_breach(self, tel):
        reg, ts, eng, total = self._world()
        bad = reg.counter("t_slo_bad", "bad")
        # a long healthy history, then a SHORT blip: the fast window
        # burns, the slow window absorbs it -> "burning", no alert
        for t in (0.0, 20.0, 40.0, 49.0):
            total.inc(250)
            ts.tick(now=t)
        total.inc(10)
        bad.inc(5)
        ts.tick(now=60.0)
        r = eng.evaluate(now=60.0)
        assert r["errors"]["state"] == "burning"
        assert eng.breached() == set()

    def test_latency_and_step_time_kinds(self, tel):
        reg = MetricsRegistry()
        ts = TimeSeriesSampler(registry=reg)
        h = reg.histogram("t_slo_lat", "lat", buckets=(0.1, 0.5, 1.0))
        eng = SLOEngine([
            {"name": "p99", "kind": "latency", "hist": "t_slo_lat",
             "threshold_s": 0.5, "target": 0.9, "windows": [10, 60]},
            {"name": "step", "kind": "step_time", "hist": "t_slo_lat",
             "budget_s": 0.3, "windows": [10, 60]},
        ], sampler=ts)
        ts.tick(now=0.0)        # zero baseline for every series
        for _ in range(95):
            h.observe(0.05)
        for _ in range(5):
            h.observe(0.8)
        ts.tick(now=5.0)
        r = eng.evaluate(now=5.0)
        # 5% slow vs a 10% budget: under
        assert r["p99"]["state"] == "ok"
        assert 0 < r["p99"]["burn_fast"] < 1.0
        # mean ~0.0875s vs 0.3s budget: well under
        assert r["step"]["state"] == "ok"
        # now a slow burst pushes both
        for _ in range(50):
            h.observe(0.8)
        ts.tick(now=8.0)
        r = eng.evaluate(now=8.0)
        assert r["p99"]["state"] == "breach"
        assert r["p99"]["burn_fast"] > 1.0

    def test_goodput_kind(self, tel):
        reg = MetricsRegistry()
        ts = TimeSeriesSampler(registry=reg)
        c = reg.counter("t_slo_rows", "rows")
        eng = SLOEngine([{
            "name": "goodput", "kind": "goodput",
            "series": "t_slo_rows_total", "min": 10.0,    # rows/sec
            "windows": [10, 60]}], sampler=ts)
        c.inc(1)
        ts.tick(now=0.0)
        c.inc(200)                      # 20/s over the 10s fast window
        ts.tick(now=10.0)
        r = eng.evaluate(now=10.0)
        assert r["goodput"]["burn_fast"] == pytest.approx(0.5)
        c.inc(10)                       # 1/s: half the floor -> burn 10
        ts.tick(now=20.0)
        r = eng.evaluate(now=20.0)
        assert r["goodput"]["burn_fast"] == pytest.approx(10.0)

    def test_from_config_and_should_shed(self, tel):
        reg = MetricsRegistry()
        ts = TimeSeriesSampler(registry=reg)
        cfg = json.dumps({"objectives": [
            {"name": "errors", "kind": "error_rate",
             "bad": "t_slo_bad_total", "total": "t_slo_requests_total",
             "target": 0.9, "windows": [10, 60],
             "shed_on_breach": True}]})
        eng = SLOEngine.from_config(cfg, sampler=ts)
        total = reg.counter("t_slo_requests", "total")
        bad = reg.counter("t_slo_bad", "bad")
        total.inc(10)
        bad.inc(9)
        ts.tick(now=0.0)
        ts2 = 5.0
        total.inc(10)
        bad.inc(9)
        ts.tick(now=ts2)
        eng.evaluate(now=ts2)
        assert eng.should_shed()
        hz = eng.healthz()
        assert hz["ok"] is False
        assert hz["objectives"]["errors"]["state"] == "breach"


# ----------------------------------------------------- straggler detection

class TestStragglerDetection:
    def test_synthetic_straggler_flagged(self):
        det = StepTimeAnomalyDetector(min_samples=8)
        rng = np.random.default_rng(0)
        for _ in range(32):
            for h in ("host0", "host1", "host2", "host3"):
                base = 0.30 if h == "host2" else 0.10
                det.observe(h, base + rng.normal(0, 0.002))
        assert det.stragglers() == {"host2"}
        rep = det.report()
        assert rep["stragglers"] == ["host2"]
        assert rep["host_median_s"]["host2"] > rep["host_median_s"]["host0"]

    def test_uniform_fleet_is_quiet(self):
        det = StepTimeAnomalyDetector(min_samples=8)
        rng = np.random.default_rng(1)
        for _ in range(32):
            for h in ("host0", "host1", "host2", "host3"):
                det.observe(h, 0.1 + rng.normal(0, 0.005))
        assert det.stragglers() == set()

    def test_min_samples_gate(self):
        det = StepTimeAnomalyDetector(min_samples=8)
        for h, v in (("a", 0.1), ("b", 10.0)):
            for _ in range(4):              # below min_samples
                det.observe(h, v)
        assert det.stragglers() == set()
        # bad samples (negative, NaN) are dropped at the door
        det.observe("a", -1.0)
        det.observe("a", float("nan"))
        assert len(det.report()["host_median_s"]) == 0

    def test_supervisor_straggler_pass(self, tel, tmp_path):
        """Heartbeat progress feeds the detector; the supervisor flags
        (advisory, never a death verdict) and surfaces everywhere."""
        from mmlspark_tpu.resilience.elastic import TrainSupervisor
        hosts = ["host0", "host1", "host2"]
        sup = TrainSupervisor(hosts, str(tmp_path), grace=1000.0)
        try:
            # synthesize heartbeat progress: host1 advances steps at a
            # third the pace of the others (same wall time, fewer steps)
            import time as _time
            t0 = _time.time()
            for k in range(24):
                for h in hosts:
                    steps = (k + 1) * (1 if h == "host1" else 3)
                    with open(tmp_path / f"hb_{h}.json", "w") as f:
                        json.dump({"host": h, "time": t0 + k,
                                   "epoch": 0, "step": steps}, f)
                sup.tick()
            assert sup.straggler_hosts() == {"host1"}
            assert sup.dead_hosts() == set()        # advisory only
            names = [e.get("name") for e in telemetry.trace.events()]
            assert "elastic/straggler" in names
        finally:
            sup.stop()


# ------------------------------------------- serving surface (end to end)

class TestServingSurface:
    def _post(self, url, data=b'{"x": 1}', timeout=10.0):
        req = urllib.request.Request(url, data=data)
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status

    def test_timeseries_endpoint(self, tel):
        """GET /timeseries serves the process-global sampler's rings."""
        from mmlspark_tpu.io.http.server import serve_pipeline
        from mmlspark_tpu.core.pipeline import Transformer
        from mmlspark_tpu.core.utils import object_column

        class Echo(Transformer):
            def transform(self, df):
                return df.withColumn("reply", object_column(
                    ["ok" for _ in df.col("value")]))

        source, loop = serve_pipeline(Echo())
        try:
            assert self._post(source.url) == 200
            telemetry.timeseries.tick()
            with urllib.request.urlopen(source.url + "timeseries",
                                        timeout=10) as r:
                doc = json.load(r)
            assert doc["schema"] == "mmlspark-timeseries/v1"
            assert any(k.startswith("mmlspark_http_replies_total")
                       for k in doc["series"])
        finally:
            loop.stop()
            source.close()
            telemetry.timeseries.clear()

    def test_slo_breach_surfaces_everywhere(self, tel, tmp_path):
        """The acceptance path: an injected-fault error burst breaches a
        shed_on_breach error-rate SLO; the breach shows up in /healthz,
        as an slo/breach instant on the trace, in a flight-recorder
        dump, and the shedder starts returning 503s."""
        from mmlspark_tpu.core.pipeline import Transformer
        from mmlspark_tpu.core.utils import object_column
        from mmlspark_tpu.io.http.server import serve_pipeline
        from mmlspark_tpu.resilience import faults

        class Echo(Transformer):
            def transform(self, df):
                return df.withColumn("reply", object_column(
                    ["ok" for _ in df.col("value")]))

        reg = telemetry.registry      # live server metrics
        ts = TimeSeriesSampler(registry=reg)
        eng = SLOEngine([{
            "name": "serving-errors", "kind": "error_rate",
            "bad": 'mmlspark_http_replies_total{code="500"}',
            "total": "mmlspark_http_replies_total",
            "target": 0.9, "windows": [5.0, 30.0],
            "shed_on_breach": True}], sampler=ts)
        telemetry.flight.enable(str(tmp_path))
        source, loop = serve_pipeline(Echo(), slo=eng)
        try:
            assert self._post(source.url) == 200
            ts.tick(now=0.0)
            assert eng.evaluate(now=0.0)[
                "serving-errors"]["state"] == "ok"
            assert source.health()["slo"]["ok"] is True
            # every transform now faults -> 500 replies burn the budget
            faults.configure("serving.transform:error:1.0", seed=0)
            for _ in range(4):
                with pytest.raises(urllib.error.HTTPError):
                    self._post(source.url)
            ts.tick(now=31.0)
            r = eng.evaluate(now=31.0)
            assert r["serving-errors"]["state"] == "breach"
            # 1. /healthz carries the verdict and flips unhealthy
            hz = source.health()
            assert hz["ok"] is False
            assert hz["slo"]["objectives"]["serving-errors"][
                "state"] == "breach"
            # 2. the active trace carries the alert instant
            names = [e.get("name") for e in telemetry.trace.events()]
            assert "slo/breach" in names
            # 3. a flight dump records the breach note
            dump = telemetry.flight.dump("test")
            with open(dump) as f:
                bundle = json.load(f)
            assert any(e.get("kind") == "note"
                       and e.get("name") == "slo/breach"
                       for e in bundle["events"])
            # 4. the shedder consults the engine: fast 503, Retry-After
            faults.clear()
            with pytest.raises(urllib.error.HTTPError) as ei:
                self._post(source.url)
            assert ei.value.code == 503
            # recovery: healthy traffic clears both windows
            eng.evaluate(now=120.0)
            assert not eng.should_shed()
            assert self._post(source.url) == 200
        finally:
            loop.stop()
            source.close()
            faults.clear()
            telemetry.flight.disable()
            telemetry.flight.clear()

    def test_trainer_slo_config_shorthand(self, tel):
        """The ``sloConfig`` param: a fit-scoped sampler + engine; an
        absurdly tight step budget must come back breached in the
        final report on the learner."""
        from mmlspark_tpu import DataFrame
        from mmlspark_tpu.core.utils import object_column
        from mmlspark_tpu.models import TpuLearner
        rng = np.random.default_rng(0)
        n = 128
        x = rng.normal(size=(n, 8)).astype(np.float32)
        y = (x[:, 0] > 0).astype(np.int64)
        df = DataFrame({"features": object_column([r for r in x]),
                        "label": y})
        lrn = (TpuLearner()
               .setModelConfig({"type": "mlp", "hidden": [8],
                                "num_classes": 2})
               .setEpochs(1).setBatchSize(32)
               .setSloConfig({"stepTimeBudget": 1e-6,
                              "windows": [0.5, 2.0], "interval": 0.05}))
        lrn.fit(df)
        rep = lrn._last_slo_report
        assert rep["breached"] == ["fit-step-time"]
        assert rep["objectives"]["fit-step-time"]["burn_fast"] > 1.0
        # a config with neither objectives nor a budget fails eagerly
        with pytest.raises(ValueError, match="sloConfig"):
            lrn.setSloConfig({"interval": 1.0}).fit(df)

    def test_sampler_lifecycle(self, tel):
        """start() is idempotent, arms telemetry, and stop() joins."""
        ts = TimeSeriesSampler(interval=0.01)
        telemetry.disable()
        try:
            ts.start()
            assert ts.running
            assert telemetry.enabled()      # arming enables telemetry
            ts.start()                      # idempotent
            ts.stop()
            assert not ts.running
        finally:
            ts.stop()
            telemetry.enable()              # hand back to the fixture
