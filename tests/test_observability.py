"""Distributed tracing (traceparent propagation + merge), device profiler
(cost analysis, compile accounting, live buffers), and the crash flight
recorder — plus the exposition-correctness satellites (label escaping,
content type, histogram boundary semantics, trace-ring drop accounting)."""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from mmlspark_tpu import telemetry
from mmlspark_tpu.telemetry import context
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
    telemetry.profiler.disable()
    telemetry.profiler.reset()
    telemetry.flight.disable()
    telemetry.flight.clear()
    telemetry.registry.reset()
    telemetry.trace.clear()


class _Echo:
    def transform(self, df):
        from mmlspark_tpu.core.utils import object_column
        return df.withColumn("reply", object_column(
            [json.dumps({"echo": v}) for v in df.col("value")]))


def _post(url, payload, headers=None, timeout=15.0):
    req = urllib.request.Request(url, data=payload.encode(),
                                 headers=headers or {})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read().decode()


# ------------------------------------------------------------ trace context

class TestSpanContext:
    def test_traceparent_round_trip(self):
        ctx = context.new_trace()
        assert len(ctx.trace_id) == 32 and len(ctx.span_id) == 16
        parsed = context.parse_traceparent(ctx.to_traceparent())
        assert parsed == ctx

    def test_malformed_headers_are_none(self):
        for bad in (None, "", "garbage", "00-abc-def-01",
                    "00-" + "0" * 32 + "-" + "1" * 16 + "-01",   # zero trace
                    "00-" + "z" * 32 + "-" + "1" * 16 + "-01"):  # non-hex
            assert context.parse_traceparent(bad) is None

    def test_child_keeps_trace_new_span(self):
        ctx = context.new_trace()
        child = ctx.child()
        assert child.trace_id == ctx.trace_id
        assert child.span_id != ctx.span_id

    def test_use_installs_and_restores(self):
        assert context.current() is None
        ctx = context.new_trace()
        with context.use(ctx):
            assert context.current() == ctx
            with context.use(context.new_trace()):
                assert context.current() != ctx
            assert context.current() == ctx
        assert context.current() is None
        # raw header + None both accepted
        with context.use(ctx.to_traceparent()):
            assert context.current() == ctx
        with context.use(None):
            assert context.current() is None

    def test_spans_tag_and_parent_under_context(self, tel):
        ctx = context.new_trace()
        with context.use(ctx):
            with tel.trace.span("outer"):
                with tel.trace.span("inner"):
                    pass
            tel.trace.instant("mark")
        evs = {e["name"]: e["args"] for e in tel.trace.events()}
        assert evs["outer"]["trace_id"] == ctx.trace_id
        assert evs["outer"]["parent_span_id"] == ctx.span_id
        assert evs["inner"]["parent_span_id"] == evs["outer"]["span_id"]
        assert evs["mark"]["trace_id"] == ctx.trace_id

    def test_span_without_context_stays_plain(self, tel):
        with tel.trace.span("plain"):
            pass
        (ev,) = recorded(tel.trace)
        assert "trace_id" not in ev.get("args", {})

    def test_complete_records_explicit_duration_child(self, tel):
        ctx = context.new_trace()
        t0 = time.perf_counter_ns()
        time.sleep(0.003)
        tel.trace.complete("hop", t0, parent=ctx.to_traceparent(), code=200)
        (ev,) = recorded(tel.trace)
        assert ev["ph"] == "X" and ev["dur"] >= 2000
        assert ev["args"]["parent_span_id"] == ctx.span_id
        assert ev["args"]["code"] == 200


class TestMergeTraces:
    def test_merge_and_filter(self, tel, tmp_path):
        ctx = context.new_trace()
        with context.use(ctx), tel.trace.span("a"):
            pass
        p1 = str(tmp_path / "p1.jsonl")
        tel.trace.export_chrome_trace(p1)
        tel.trace.clear()
        with tel.trace.span("unrelated"):
            pass
        with context.use(ctx.child()), tel.trace.span("b"):
            pass
        p2 = str(tmp_path / "p2.json")
        tel.trace.export_chrome_trace(p2, array=True)   # both forms load
        merged = telemetry.merge_traces([p1, p2],
                                        str(tmp_path / "merged.jsonl"))
        assert {e["name"] for e in merged} == {"a", "unrelated", "b",
                                               ANCHOR_EVENT}
        only = telemetry.merge_traces([p1, p2], trace_id=ctx.trace_id)
        assert {e["name"] for e in only} == {"a", "b"}
        # merged file is valid JSONL
        lines = [json.loads(line)
                 for line in open(tmp_path / "merged.jsonl")]
        assert len(lines) == 5     # a cleared ring anchors afresh


# -------------------------------------------- server -> worker -> reply hop

class TestDistributedRequestTrace:
    def test_traceparent_round_trip_across_fleet_hops(self, tel):
        """One request through the in-process fleet (client -> worker
        ingress -> driver poll -> transform -> reply): every recorded hop
        shares the client's trace_id and parents under the ingress span."""
        from mmlspark_tpu.io.http.fleet import (ProcessHTTPSource,
                                                ReplayServingLoop, _Worker)
        from mmlspark_tpu.io.http.worker import WorkerServer
        ws = WorkerServer("127.0.0.1")
        src = ProcessHTTPSource(workers=[
            _Worker("127.0.0.1", ws.source.port, ws.control_port,
                    spawn=False)])
        loop = ReplayServingLoop(src, _Echo()).start()
        try:
            client = context.new_trace()
            code, body = _post(
                f"http://127.0.0.1:{ws.source.port}/", "ping",
                headers={"traceparent": client.to_traceparent()})
            assert code == 200 and json.loads(body)["echo"] == "ping"
            deadline = time.monotonic() + 5
            names = {}
            while time.monotonic() < deadline:
                names = {e["name"]: e["args"] for e in tel.trace.events()
                         if (e.get("args") or {}).get("trace_id")
                         == client.trace_id}
                if {"http/request", "fleet/request",
                        "serve/request"} <= set(names):
                    break
                time.sleep(0.02)
            assert {"http/request", "fleet/request",
                    "serve/request"} <= set(names), names.keys()
            ingress = names["http/request"]
            # the ingress span is a child of the CLIENT's span; the
            # driver + reply hops are children of the ingress span
            assert ingress["parent_span_id"] == client.span_id
            assert names["fleet/request"]["parent_span_id"] \
                == ingress["span_id"]
            assert names["serve/request"]["parent_span_id"] \
                == ingress["span_id"]
        finally:
            loop.stop()
            ws.close()

    def test_fresh_trace_minted_without_header(self, tel):
        from mmlspark_tpu.io.http.server import serve_pipeline
        src, loop = serve_pipeline(_Echo())
        try:
            code, _ = _post(src.url, "x")
            assert code == 200
            # the handler closes its span after the reply has gone out:
            # wait for the event, as the fleet test above does
            deadline = time.monotonic() + 2
            reqs = []
            while not reqs and time.monotonic() < deadline:
                reqs = [e for e in tel.trace.events()
                        if e["name"] == "http/request"]
                time.sleep(0.02)
            assert reqs and "trace_id" in reqs[0]["args"]
        finally:
            loop.stop()
            src.close()

    def test_http_transformer_propagates_traceparent(self, tel):
        """Outbound HTTPTransformer requests carry the caller's trace as
        a traceparent header under an http/client child span."""
        from mmlspark_tpu.core.dataframe import DataFrame
        from mmlspark_tpu.core.utils import object_column
        from mmlspark_tpu.io.http.server import HTTPSource
        from mmlspark_tpu.io.http.transformer import HTTPTransformer
        seen = {}
        upstream = HTTPSource()

        def server_side():
            batch = upstream.getBatch(4, timeout=5.0)
            for ex_id in batch.col("id"):
                seen["trace"] = upstream.trace_for(str(ex_id))
                upstream.respond(str(ex_id), 200, "{}")
        t = threading.Thread(target=server_side, daemon=True)
        t.start()
        ctx = context.new_trace()
        df = DataFrame({"req": object_column(
            [{"url": upstream.url, "method": "POST", "body": "{}"}])})
        with context.use(ctx):
            out = (HTTPTransformer().setInputCol("req").setOutputCol("resp")
                   .transform(df))
        t.join(timeout=10)
        assert out.col("resp")[0]["statusCode"] == 200
        # the upstream server parsed OUR trace id from the wire header
        got = context.parse_traceparent(seen["trace"])
        assert got is not None and got.trace_id == ctx.trace_id
        names = [e["name"] for e in tel.trace.events()]
        assert "http/client" in names
        upstream.close()

    def test_retry_instants_tag_owning_trace(self, tel):
        from mmlspark_tpu.resilience.policy import RetryPolicy
        ctx = context.new_trace()
        calls = {"n": 0}

        def flaky(_a):
            calls["n"] += 1
            if calls["n"] == 1:
                raise ConnectionError("blip")
            return "ok"
        with context.use(ctx):
            assert RetryPolicy(name="t.obs", base_delay=0.0,
                               max_delay=0.0).run(flaky) == "ok"
        retries = [e for e in tel.trace.events() if e["name"] == "retry"]
        assert retries
        assert retries[0]["args"]["trace_id"] == ctx.trace_id


# ----------------------------------------------------------------- profiler

class TestProfiler:
    def test_double_compile_shape_change(self, tel):
        import jax
        import jax.numpy as jnp
        prof = telemetry.profiler
        prof.enable()
        pf = prof.wrap(jax.jit(lambda a: (a @ a.T).sum()), "t.obs.fn")
        pf(jnp.ones((8, 8), jnp.float32))
        pf(jnp.ones((8, 8), jnp.float32))       # cached: no recompile
        pf(jnp.ones((16, 16), jnp.float32))     # shape change: recompile
        rep = prof.report()["functions"]["t.obs.fn"]
        assert rep["compiles"] == 2
        assert rep["recompile_causes"] == {"first": 1, "shape_change": 1}
        assert rep["flops_per_call"] > 0
        assert rep["bytes_per_call"] > 0
        assert rep["compile_seconds"] > 0
        assert rep["calls"] == 3
        assert rep["achieved_flops_per_sec"] > 0
        # the cpu backend claims no peak: no utilization, no gauge series
        assert rep["roofline_utilization"] is None
        assert not telemetry.snapshot().get(
            "mmlspark_profiler_roofline_utilization", {}).get("series")
        # a pinned peak brings the gauge back; an unknown accelerator kind
        # is an error, never an invented denominator
        prof.set_peak_flops(1e15)
        try:
            pf(jnp.ones((8, 8), jnp.float32))
            assert 0 < prof.report()["functions"]["t.obs.fn"][
                "roofline_utilization"] < 1
        finally:
            prof.set_peak_flops(None)
        # counters landed in the shared registry too
        snap = telemetry.snapshot()
        series = snap["mmlspark_profiler_compiles"]["series"]
        by_cause = {s["labels"]["cause"]: s["value"] for s in series
                    if s["labels"]["fn"] == "t.obs.fn"}
        assert by_cause == {"first": 1, "shape_change": 1}
        # compile spans recorded
        assert any(e["name"] == "fit/compile"
                   for e in tel.trace.events())

    def test_live_buffer_gauge(self, tel):
        import jax.numpy as jnp
        prof = telemetry.profiler
        prof.enable()
        keep = jnp.ones((256, 256), jnp.float32)  # noqa: F841 held live
        total = prof.sample_live_buffers()
        assert total >= keep.nbytes
        assert prof.report()["live_buffer_peak_bytes"] >= keep.nbytes

    def test_disabled_is_passthrough(self, tel):
        import jax
        prof = telemetry.profiler
        assert not prof.enabled()
        pf = prof.wrap(jax.jit(lambda a: a + 1), "t.obs.off")
        out = pf(np.zeros(4, np.float32))
        assert out.shape == (4,)
        assert prof.sample_live_buffers() == 0.0
        assert "t.obs.off" not in prof.report()["functions"]

    def test_learner_profile_param(self, tel):
        """TpuLearner(profile=True): the fit's dispatches run through the
        profiler — compile accounting + cost analysis + HBM peak."""
        from mmlspark_tpu.core.dataframe import DataFrame
        from mmlspark_tpu.core.utils import object_column
        from mmlspark_tpu.models.trainer import TpuLearner
        rng = np.random.default_rng(0)
        n = 64
        df = DataFrame({
            "features": object_column(
                [rng.normal(size=8).astype(np.float32) for _ in range(n)]),
            "label": rng.integers(0, 2, n).astype(np.int64)})
        (TpuLearner()
         .setModelConfig({"type": "mlp", "hidden": [8], "num_classes": 2})
         .setEpochs(1).setBatchSize(32).setProfile(True).fit(df))
        rep = telemetry.profiler.report()
        tags = [t for t in rep["functions"] if t.startswith("trainer.")]
        assert tags, rep
        fn = rep["functions"][tags[0]]
        assert fn["compiles"] >= 1 and fn["flops_per_call"] > 0
        assert rep["live_buffer_peak_bytes"] > 0


# ----------------------------------------------------------- flight recorder

class TestFlightRecorder:
    def test_dump_on_injected_fault(self, tel, tmp_path):
        """Chaos scenario: fault injected into the serving transform, the
        loop's retry recovers the request, and the flight bundle (file +
        GET /debug/flight) carries the fault instant + recent spans."""
        from mmlspark_tpu.io.http.server import serve_pipeline
        from mmlspark_tpu.resilience import faults
        telemetry.flight.enable(str(tmp_path))
        faults.configure("serving.transform:error:1.0:0:1", seed=0)
        src, loop = serve_pipeline(_Echo())
        try:
            code, body = _post(src.url, "survive")
            assert code == 200 and json.loads(body)["echo"] == "survive"
            with urllib.request.urlopen(src.url + "debug/flight",
                                        timeout=5) as r:
                assert r.status == 200
                bundle = json.loads(r.read())
            kinds = {e["kind"] for e in bundle["events"]}
            assert "instant" in kinds or "span" in kinds
            names = [e.get("name") for e in bundle["events"]]
            assert "fault/injected" in names
            assert any(n in ("serve/batch", "http/request",
                             "serve/request") for n in names)
            # select the armed site's series: earlier tests may have
            # minted label children for other sites (value 0 after the
            # registry reset), so series[0] is not necessarily ours
            assert sum(
                s["value"] for s in bundle["metrics"][
                    "mmlspark_faults_injected_total"]["series"]
                if s.get("labels", {}).get("site")
                in (None, "serving.transform")) >= 1
            # explicit dump writes the same bundle to disk
            path = telemetry.flight.dump("test")
            doc = json.loads(open(path).read())
            assert doc["reason"] == "test"
            assert str(tmp_path) in path
        finally:
            loop.stop()
            src.close()
            faults.clear()

    def test_note_and_metric_delta_samples(self, tel):
        telemetry.flight.enable()
        telemetry.flight.note("supervisor_verdict", worker=0, dead=True)
        c = tel.registry.counter("t_obs_flight_c")
        c.inc(5)
        # force a second sample window
        telemetry.flight._last_sample = 0.0
        telemetry.flight.note("later")
        b = telemetry.flight.bundle()
        notes = [e for e in b["events"] if e["kind"] == "note"]
        assert notes and notes[0]["name"] == "supervisor_verdict"
        deltas = [e for e in b["events"] if e["kind"] == "metrics"]
        assert any(d["delta"].get("t_obs_flight_c") == 5 for d in deltas)

    def test_excepthook_chain_dumps_then_delegates(self, tel, tmp_path):
        import sys
        telemetry.flight.enable(str(tmp_path))
        called = {}
        prev = sys.excepthook
        telemetry.flight._prev_excepthook = \
            lambda *a: called.setdefault("prev", a)
        try:
            telemetry.flight._excepthook(ValueError, ValueError("boom"),
                                         None)
        finally:
            sys.excepthook = prev
        assert called["prev"][0] is ValueError
        doc = json.loads(
            open(tmp_path / f"flight_{telemetry.flight.bundle()['pid']}"
                            ".json").read())
        assert doc["reason"] == "excepthook"
        assert any(e.get("name") == "unhandled_exception"
                   for e in doc["events"])

    def test_flight_env_parsing(self, monkeypatch):
        from mmlspark_tpu.core import env
        monkeypatch.delenv("MMLSPARK_TPU_FLIGHT", raising=False)
        assert env.flight_path() is None
        monkeypatch.setenv("MMLSPARK_TPU_FLIGHT", "0")
        assert env.flight_path() is None
        monkeypatch.setenv("MMLSPARK_TPU_FLIGHT", "1")
        assert env.flight_path() == ""
        monkeypatch.setenv("MMLSPARK_TPU_FLIGHT", "/tmp/flightdir")
        assert env.flight_path() == "/tmp/flightdir"


# ------------------------------------------------- exposition satellites

class TestExpositionCorrectness:
    def test_label_values_escaped(self, tel):
        c = tel.registry.counter("t_obs_esc", "esc", labels=("k",))
        c.labels(k='a"b\\c\nd').inc()
        text = tel.registry.prometheus_text()
        line = [l for l in text.splitlines()
                if l.startswith("t_obs_esc_total")][0]
        assert line == 't_obs_esc_total{k="a\\"b\\\\c\\nd"} 1'
        # the exposition stays line-parseable
        assert "\nd" not in line

    def test_metrics_content_type_charset(self, tel):
        from mmlspark_tpu.io.http.server import serve_pipeline
        src, loop = serve_pipeline(_Echo())
        try:
            with urllib.request.urlopen(src.url + "metrics",
                                        timeout=5) as r:
                assert r.headers["Content-Type"] == \
                    "text/plain; version=0.0.4; charset=utf-8"
        finally:
            loop.stop()
            src.close()

    def test_histogram_boundary_le_semantics(self, tel):
        """A value equal to a bucket bound lands in the bucket whose
        ``le`` it equals (Prometheus <= semantics), for every bound."""
        h = tel.registry.histogram("t_obs_edge", buckets=(0.1, 1.0, 10.0))
        for v in (0.1, 1.0, 10.0):
            h.observe(v)
        cum = h.bucket_counts()
        assert cum[0.1] == 1          # 0.1 <= 0.1
        assert cum[1.0] == 2          # cumulative: 0.1 and 1.0
        assert cum[10.0] == 3
        assert cum[float("inf")] == 3
        # just past a bound goes one bucket up; under stays put
        h2 = tel.registry.histogram("t_obs_edge2", buckets=(1.0, 2.0))
        h2.observe(1.0000001)
        h2.observe(0.9999999)
        cum2 = h2.bucket_counts()
        assert cum2[1.0] == 1 and cum2[2.0] == 2
        # exposition agrees
        text = tel.registry.prometheus_text()
        assert 't_obs_edge_bucket{le="0.1"} 1' in text

    def test_tracer_drop_counter_and_truncated_metadata(self, tel,
                                                        tmp_path):
        small = telemetry.Tracer(max_events=5)
        for i in range(8):      # and the anchor the first of them brings
            with small.span("s", i=i):
                pass
        assert small.dropped() == 4
        assert tel.registry.counter(
            "mmlspark_telemetry_events_dropped").value == 4
        path = str(tmp_path / "trunc.jsonl")
        n = small.export_chrome_trace(path)
        evs = [json.loads(line) for line in open(path)]
        assert n == len(evs) == 6    # 5 events + 1 metadata
        meta = evs[0]
        assert meta["ph"] == "M"
        assert meta["args"] == {"truncated": True, "dropped": 4}
        # an un-truncated tracer exports no metadata event
        ok = telemetry.Tracer(max_events=50)
        with ok.span("fine"):
            pass
        path2 = str(tmp_path / "ok.jsonl")
        ok.export_chrome_trace(path2)
        evs2 = [json.loads(line) for line in open(path2)]
        assert [e["name"] for e in evs2] == [ANCHOR_EVENT, "fine"]
        assert all(e["ph"] != "M" for e in evs2)
        # clear resets the drop accounting
        small.clear()
        assert small.dropped() == 0
