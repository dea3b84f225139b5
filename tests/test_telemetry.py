"""Runtime telemetry: registry semantics, span tracing + Chrome-trace
export, the serving /metrics scrape surface, and hot-path instrumentation
smoke (trainer + GBDT populate metrics after one fit)."""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from mmlspark_tpu import telemetry
from mmlspark_tpu.telemetry.tracer import ANCHOR_EVENT


def recorded(tracer):
    """The ring less its `clock/anchor` events (the first event recorded
    brings one, and the first of every later second)."""
    return [e for e in tracer.events() if e["name"] != ANCHOR_EVENT]


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


# ---------------------------------------------------------------- registry

class TestRegistry:
    def test_counter_inc_and_identity(self, tel):
        c = tel.registry.counter("t_requests", "help text")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        # get-or-create: same family object on re-registration
        assert tel.registry.counter("t_requests") is c
        with pytest.raises(ValueError):
            c.inc(-1)
        with pytest.raises(ValueError):  # name/kind clash
            tel.registry.gauge("t_requests")

    def test_labels_are_independent_series(self, tel):
        c = tel.registry.counter("t_errs", "errs", labels=("worker",))
        c.labels(worker="0").inc()
        c.labels(worker="0").inc()
        c.labels(worker="1").inc(5)
        assert c.labels(worker="0").value == 2
        assert c.labels(worker="1").value == 5
        with pytest.raises(ValueError):
            c.labels(bogus="x")
        text = tel.registry.prometheus_text()
        assert 't_errs_total{worker="0"} 2' in text
        assert 't_errs_total{worker="1"} 5' in text

    def test_gauge(self, tel):
        g = tel.registry.gauge("t_depth")
        g.set(7)
        g.inc()
        g.dec(3)
        assert g.value == 5
        assert "t_depth 5" in tel.registry.prometheus_text()

    def test_histogram_buckets_sum_count(self, tel):
        h = tel.registry.histogram("t_lat", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        assert h.count == 5
        assert h.sum == pytest.approx(56.05)
        cum = h.bucket_counts()
        assert cum[0.1] == 1 and cum[1.0] == 3 and cum[10.0] == 4
        assert cum[float("inf")] == 5
        text = tel.registry.prometheus_text()
        assert 't_lat_bucket{le="0.1"} 1' in text
        assert 't_lat_bucket{le="+Inf"} 5' in text
        assert "t_lat_count 5" in text
        # boundary value lands in its own bucket (le semantics)
        h2 = tel.registry.histogram("t_edge", buckets=(1.0,))
        h2.observe(1.0)
        assert h2.bucket_counts()[1.0] == 1

    def test_snapshot_is_jsonable(self, tel):
        tel.registry.counter("t_c").inc()
        tel.registry.histogram("t_h").observe(0.2)
        snap = json.loads(json.dumps(tel.snapshot()))
        assert snap["t_c"]["series"][0]["value"] == 1
        assert snap["t_h"]["series"][0]["count"] == 1

    def test_disabled_is_noop(self, tel):
        c = tel.registry.counter("t_off")
        h = tel.registry.histogram("t_off_h")
        g = tel.registry.gauge("t_off_g")
        tel.disable()
        c.inc()
        h.observe(1.0)
        g.set(9)
        with h.time():
            pass
        assert c.value == 0 and h.count == 0 and g.value == 0
        assert not recorded(tel.trace)
        with tel.trace.span("never"):
            pass
        tel.trace.instant("never")
        assert recorded(tel.trace) == [] and len(tel.trace.anchors()) == 1

    def test_thread_safety(self, tel):
        c = tel.registry.counter("t_mt")
        h = tel.registry.histogram("t_mt_h", buckets=(0.5,))

        def work():
            for _ in range(1000):
                c.inc()
                h.observe(0.1)

        ts = [threading.Thread(target=work) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.value == 8000
        assert h.count == 8000
        assert h.bucket_counts()[0.5] == 8000


# ------------------------------------------------------------------ tracer

class TestTracer:
    def test_span_nesting_and_roundtrip(self, tel, tmp_path):
        with tel.trace.span("outer", kind="test"):
            with tel.trace.span("inner", step=1):
                time.sleep(0.002)
        path = str(tmp_path / "trace.jsonl")
        n = tel.trace.export_chrome_trace(path)
        evs = [json.loads(line) for line in open(path)]
        assert n == len(evs) == 3
        assert evs.pop(0)["name"] == ANCHOR_EVENT
        by_name = {e["name"]: e for e in evs}
        inner, outer = by_name["inner"], by_name["outer"]
        for e in evs:
            assert e["ph"] == "X" and "pid" in e and "tid" in e
        # time containment = nesting in chrome://tracing / Perfetto
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
        assert inner["args"]["step"] == 1
        assert outer["args"]["kind"] == "test"

    def test_array_export_is_valid_json(self, tel, tmp_path):
        with tel.trace.span("a"):
            pass
        path = str(tmp_path / "trace.json")
        tel.trace.export_chrome_trace(path, array=True)
        evs = json.loads(open(path).read())
        assert [e["name"] for e in evs] == [ANCHOR_EVENT, "a"]

    def test_sync_point_blocks_on_jax_value(self, tel):
        import jax.numpy as jnp
        with tel.trace.span("compute") as sp:
            v = jnp.arange(8).sum()
            sp.set_sync(v)
        (ev,) = recorded(tel.trace)
        assert ev["name"] == "compute"

    def test_buffer_is_bounded(self, tel):
        small = telemetry.Tracer(max_events=10)
        from mmlspark_tpu.telemetry.registry import _state
        assert _state.enabled
        for i in range(50):
            with small.span("s", i=i):
                pass
        evs = small.events()
        assert len(evs) == 10
        assert evs[-1]["args"]["i"] == 49


# --------------------------------------------------------------- /metrics

class _Echo:
    def transform(self, df):
        from mmlspark_tpu.core.utils import object_column
        return df.withColumn("reply", object_column(
            [json.dumps({"echo": v}) for v in df.col("value")]))


def _post(url, payload, timeout=10.0):
    req = urllib.request.Request(url, data=payload.encode(),
                                 headers={"Content-Type": "text/plain"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read().decode()


def _scrape(url, timeout=5.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        assert r.status == 200
        assert r.headers["Content-Type"].startswith("text/plain")
        return r.read().decode()


class TestMetricsEndpoint:
    def test_serving_loop_scrape(self, tel):
        from mmlspark_tpu.io.http.server import serve_pipeline
        src, loop = serve_pipeline(_Echo())
        try:
            code, body = _post(src.url, "ping")
            assert code == 200 and json.loads(body)["echo"] == "ping"
            text = _scrape(src.url + "metrics")
            # request-latency histogram with at least the one request
            assert "mmlspark_http_request_seconds_bucket" in text
            count = [l for l in text.splitlines()
                     if l.startswith("mmlspark_http_request_seconds_count")]
            assert count and float(count[0].split()[-1]) >= 1
            # queue-depth gauge + batch-size histogram present
            assert "mmlspark_http_queue_depth" in text
            assert "mmlspark_serving_batch_rows_bucket" in text
            # unknown GET paths still 404
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(src.url + "nope", timeout=5)
        finally:
            loop.stop()
            src.close()

    def test_worker_server_scrape_in_process(self, tel):
        """The fleet's serving unit (WorkerServer) exposes /metrics on
        both its public and control ports."""
        from mmlspark_tpu.io.http.worker import WorkerServer
        w = WorkerServer("127.0.0.1")
        try:
            done = {}

            def client():
                done["r"] = _post(f"http://127.0.0.1:{w.source.port}/",
                                  "payload", timeout=15)

            t = threading.Thread(target=client)
            t.start()
            # drain + reply through the control channel
            deadline = time.monotonic() + 10
            rows = []
            while not rows and time.monotonic() < deadline:
                req = urllib.request.Request(
                    f"http://127.0.0.1:{w.control_port}/poll",
                    data=json.dumps({"max": 10, "timeout": 0.05}).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=10) as r:
                    rows = json.loads(r.read())["rows"]
            for ex_id, _ in rows:
                w.source.respond(str(ex_id), 200, "ok")
            t.join(timeout=10)
            assert done["r"][0] == 200
            for port in (w.source.port, w.control_port):
                text = _scrape(f"http://127.0.0.1:{port}/metrics")
                assert "mmlspark_http_request_seconds_bucket" in text
                assert "mmlspark_http_queue_depth" in text
        finally:
            w.close()

    @pytest.mark.extended
    def test_fleet_process_scrape(self, tel, monkeypatch):
        """GET /metrics against a live fleet: each worker PROCESS serves
        its own registry on its public port (telemetry enabled in the
        child via the inherited MMLSPARK_TPU_TELEMETRY env)."""
        monkeypatch.setenv("MMLSPARK_TPU_TELEMETRY", "1")
        from mmlspark_tpu.io.http.fleet import (ProcessHTTPSource,
                                                ReplayServingLoop)
        src, loop = None, None
        try:
            src = ProcessHTTPSource(n_workers=2)
            loop = ReplayServingLoop(src, _Echo()).start()
            for i, url in enumerate(src.urls):
                code, body = _post(url, f"m-{i}")
                assert code == 200 and json.loads(body)["echo"] == f"m-{i}"
            for url in src.urls:
                text = _scrape(url + "metrics")
                assert "mmlspark_http_request_seconds_bucket" in text
                count = [l for l in text.splitlines() if
                         l.startswith("mmlspark_http_request_seconds_count")]
                assert count and float(count[0].split()[-1]) >= 1
                assert "mmlspark_http_queue_depth" in text
            # driver-side fleet metrics recorded batches
            snap = telemetry.snapshot()
            assert snap["mmlspark_serving_batch_rows"]["series"][0][
                "count"] >= 1
        finally:
            if loop:
                loop.stop()
            elif src:
                src.close()


# ------------------------------------------------- instrumentation smoke

class TestInstrumentationSmoke:
    def test_trainer_fit_populates_metrics_and_trace(self, tel, tmp_path):
        from mmlspark_tpu.core.dataframe import DataFrame
        from mmlspark_tpu.core.utils import object_column
        from mmlspark_tpu.models.trainer import TpuLearner
        rng = np.random.default_rng(0)
        n = 64
        df = DataFrame({
            "features": object_column(
                [rng.normal(size=8).astype(np.float32) for _ in range(n)]),
            "label": rng.integers(0, 2, n).astype(np.int64)})
        learner = (TpuLearner()
                   .setModelConfig({"type": "mlp", "hidden": [8],
                                    "num_classes": 2})
                   .setEpochs(2).setBatchSize(32))
        learner.fit(df)
        snap = telemetry.snapshot()
        assert snap["mmlspark_trainer_step_seconds"]["series"][0]["count"] > 0
        assert snap["mmlspark_trainer_rows_per_sec"]["series"][0]["value"] > 0
        names = [e["name"] for e in telemetry.trace.events()]
        assert "fit" in names and "fit/step" in names
        # chrome-trace file with nested fit/step spans (acceptance)
        path = str(tmp_path / "fit_trace.jsonl")
        telemetry.trace.export_chrome_trace(path)
        evs = [json.loads(line) for line in open(path)]
        fit = next(e for e in evs if e["name"] == "fit")
        steps = [e for e in evs if e["name"] == "fit/step"]
        assert steps
        for s in steps:
            assert fit["ts"] <= s["ts"]
            assert s["ts"] + s["dur"] <= fit["ts"] + fit["dur"]

    def test_trainer_recompile_counter(self, tel):
        from mmlspark_tpu.models import trainer as tr
        tr._seen_step_sigs.clear()
        base = tr._m_recompiles.value
        a = np.zeros((8, 4), np.float32)
        tr._note_step_signature("t", a, a)
        tr._note_step_signature("t", a, a)          # same shapes: no bump
        tr._note_step_signature("t", np.zeros((16, 4), np.float32), a)
        assert tr._m_recompiles.value == base + 2

    def test_gbdt_fit_populates_metrics_and_spans(self, tel):
        from mmlspark_tpu.models.gbdt.engine import GBDTParams, fit_gbdt
        rng = np.random.default_rng(0)
        x = rng.normal(size=(256, 4)).astype(np.float32)
        y = (x[:, 0] > 0).astype(np.float32)
        fit_gbdt(x, y, GBDTParams(num_iterations=3, max_depth=3))
        snap = telemetry.snapshot()
        assert snap["mmlspark_gbdt_iterations"]["series"][0]["value"] == 3
        assert snap["mmlspark_gbdt_iter_seconds"]["series"][0]["count"] == 3
        assert snap["mmlspark_gbdt_bin_seconds"]["series"][0]["count"] == 1
        names = [e["name"] for e in telemetry.trace.events()]
        assert "gbdt/fit" in names and "gbdt/bin" in names
        assert "gbdt/iter/step" in names or "gbdt/iter/build" in names

    def test_gbdt_predict_sets_table_gauge(self, tel):
        from mmlspark_tpu.models.gbdt.engine import (GBDTParams, fit_gbdt,
                                                     predict_raw)
        rng = np.random.default_rng(0)
        x = rng.normal(size=(256, 4)).astype(np.float32)
        y = (x[:, 0] > 0).astype(np.float32)
        ens = fit_gbdt(x, y, GBDTParams(num_iterations=2, max_depth=3))
        predict_raw(ens, x)
        snap = telemetry.snapshot()
        assert snap["mmlspark_gbdt_predict_table_bytes"]["series"][0][
            "value"] > 0

    def test_mesh_put_metrics(self, tel):
        import jax
        from mmlspark_tpu.parallel import mesh as meshlib
        mesh = meshlib.create_mesh()
        arr = np.zeros((16, 4), np.float32)
        meshlib.shard_batch(arr, mesh)
        meshlib.put_global_batch(arr, mesh)
        snap = telemetry.snapshot()
        assert snap["mmlspark_mesh_put_bytes"]["series"][0]["value"] \
            == 2 * arr.nbytes
        assert snap["mmlspark_mesh_put_seconds"]["series"][0]["count"] == 2

    def test_warn_once_logs_once_counts_every(self, tel, caplog):
        import logging
        from mmlspark_tpu import telemetry as t
        t._warned_keys.discard("test-key")
        logger = logging.getLogger("mmlspark_tpu.test")
        with caplog.at_level(logging.WARNING, "mmlspark_tpu.test"):
            t.warn_once(logger, "test-key", "warned %d", 1)
            t.warn_once(logger, "test-key", "warned %d", 2)
        assert len([r for r in caplog.records
                    if "warned" in r.message]) == 1
        fam = t.registry.counter("mmlspark_warnings_total")
        assert fam.labels(key="test-key").value == 2


class TestWireDtypeGuard:
    def test_int64_overflow_rejected(self, tel):
        from mmlspark_tpu.models.tpu_model import _coerce_wire_dtype
        ok = _coerce_wire_dtype(np.array([1, 2], np.int64))
        assert ok.dtype == np.int32
        with pytest.raises(ValueError, match="int32 transfer range"):
            _coerce_wire_dtype(np.array([2 ** 40], np.int64))

    def test_float64_downcast_warns_and_counts(self, tel):
        from mmlspark_tpu import telemetry as t
        from mmlspark_tpu.models.tpu_model import _coerce_wire_dtype
        before = t.registry.counter("mmlspark_warnings_total") \
            .labels(key="wire-dtype-downcast").value
        out = _coerce_wire_dtype(np.array([1.5], np.float64))
        assert out.dtype == np.float32
        after = t.registry.counter("mmlspark_warnings_total") \
            .labels(key="wire-dtype-downcast").value
        assert after == before + 1


class TestEnvWiring:
    def test_env_switch(self, monkeypatch):
        from mmlspark_tpu.core import env
        monkeypatch.delenv("MMLSPARK_TPU_TELEMETRY", raising=False)
        assert not env.telemetry_enabled()
        for v in ("1", "true", "YES", "on"):
            monkeypatch.setenv("MMLSPARK_TPU_TELEMETRY", v)
            assert env.telemetry_enabled()
        monkeypatch.setenv("MMLSPARK_TPU_TELEMETRY", "0")
        assert not env.telemetry_enabled()
        monkeypatch.setenv("MMLSPARK_TPU_TRACE", "/tmp/x.jsonl")
        assert env.telemetry_trace_path() == "/tmp/x.jsonl"
