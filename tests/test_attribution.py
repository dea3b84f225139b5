"""Per-request latency attribution: the phase ledger threaded through
the continuous serving path (phase spans summing to the client-observed
latency), tail-based trace sampling (retention verdicts, ring-overflow
pinning, TTL expiry), OpenMetrics exemplars on latency histograms end to
end through fleet federation, and the ``/debug/trace/<id>`` fetch
surface on worker control ports and the fleet driver."""

import base64
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

from mmlspark_tpu import telemetry
from mmlspark_tpu.io.http.server import HTTPSource
from mmlspark_tpu.io.serving import (BucketPolicy, FusedServingStep,
                                     serve_continuous)
from mmlspark_tpu.models.modules import build_model
from mmlspark_tpu.telemetry import context as tracectx
from mmlspark_tpu.telemetry.federation import FederatedSampler
from mmlspark_tpu.telemetry.ledger import PHASES, PhaseLedger
from mmlspark_tpu.telemetry.timeseries import TimeSeriesSampler

T0 = 1000.0


@pytest.fixture
def tel():
    telemetry.registry.reset()
    telemetry.trace.clear()
    telemetry.enable()
    yield telemetry
    telemetry.trace.disable_tail_sampling()
    telemetry.disable()
    telemetry.registry.reset()
    telemetry.trace.clear()


def _counter_total(name):
    snap = telemetry.snapshot()
    return sum(s["value"] for s in snap.get(name, {}).get("series", []))


# the shared tiny model: 6-feature MLP, 3 classes, f32 wire rows
_CFG = {"type": "mlp", "hidden": [8], "num_classes": 3}
_ROW = (6,)


@pytest.fixture(scope="module")
def tiny_params():
    module = build_model(_CFG)
    return module.init(jax.random.PRNGKey(0),
                       np.zeros((1,) + _ROW, np.float32))


def _payload(row: np.ndarray) -> bytes:
    return base64.b64encode(np.asarray(row, np.float32).tobytes())


def _post(url, data: bytes, timeout=30.0):
    req = urllib.request.Request(url, data=data)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read().decode()


def _get_json(url, timeout=10.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, json.loads(r.read())


# ------------------------------------------------------------ ledger unit

class TestPhaseLedger:
    def test_spans_partition_the_timeline(self):
        led = PhaseLedger(t0_ns=1_000)
        t = 1_000
        for phase in PHASES:
            t += 500
            led.mark(phase, t_ns=t)
        spans = list(led.spans_ns())
        assert [s[0] for s in spans] == list(PHASES)
        # contiguous: each phase starts where the previous ended
        prev = 1_000
        for _, start, end in spans:
            assert start == prev and end == start + 500
            prev = end
        assert led.phase_s("device") == pytest.approx(500 / 1e9)
        assert led.span_s("pad", "reply") == pytest.approx(4 * 500 / 1e9)
        assert led.elapsed_s("form") == pytest.approx(2 * 500 / 1e9)
        assert led.total_s() == pytest.approx(len(PHASES) * 500 / 1e9)
        # the partition property the whole PR hangs on
        assert sum(led.as_dict().values()) == pytest.approx(led.total_s())

    def test_partial_ledger_answers_none(self):
        led = PhaseLedger(t0_ns=0)
        assert led.elapsed_s() is None and led.total_s() is None
        led.mark("queue", t_ns=10)
        led.mark("form", t_ns=30)
        assert led.phase_s("device") is None
        assert led.span_s("pad", "reply") is None
        assert led.elapsed_s("nope") is None
        assert led.as_dict() == {"queue": 10 / 1e9, "form": 20 / 1e9}


# --------------------------------------------- serving end-to-end (tentpole)

class TestPhaseAttributionE2E:
    def test_phase_sum_reconciles_and_trace_is_fetchable(self, tel,
                                                         tiny_params):
        """The acceptance pin: clean traffic stamps every phase of every
        request, requests clearing the (epsilon-seeded) slow quantile
        are tail-retained, each retained request's serve/phase spans
        partition its serve/request span (all eight, in order, each
        beginning where the last ended, the first at admission, the last
        ending inside the request), the lone request's trace_id rides
        the latency histogram as an exemplar, and GET /debug/trace/<id>
        serves the span tree. Asserted from the stamps the program
        wrote, never from a ratio of two wall-clock sums."""
        step = FusedServingStep(
            _CFG, tiny_params,
            policy=BucketPolicy(max_batch=32, min_bucket=8),
            row_shape=_ROW, in_dtype=np.float32, output="argmax")
        step.compile_buckets()      # no compile latency inside the run
        telemetry.trace.enable_tail_sampling(quantile=0.0, min_samples=8)
        # seed the latency window with epsilon completions: every real
        # request then clears the slow quantile deterministically, so
        # all nine traces below are retained
        for _ in range(8):
            telemetry.trace.tail_complete(tracectx.new_trace().trace_id,
                                          latency_s=1e-6)
        source, loop = serve_continuous(step, max_wait=0.05)
        rng = np.random.default_rng(0)
        try:
            codes = []

            def client():
                row = rng.normal(size=_ROW).astype(np.float32)
                codes.append(_post(source.url, _payload(row))[0])

            # full 8-bucket burst, then one lone straggler (its own batch)
            threads = [threading.Thread(target=client) for _ in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            assert codes == [200] * 8
            assert _post(source.url,
                         _payload(np.zeros(_ROW, np.float32)))[0] == 200
            deadline = time.monotonic() + 5
            while (len(telemetry.trace.retained_ids()) < 9
                   and time.monotonic() < deadline):
                time.sleep(0.01)    # verdict lands after the reply write
            tids = telemetry.trace.retained_ids()
            assert len(tids) == 9, "requests were never tail-retained"
            tid = tids[-1]           # the lone request: oldest-first order
            assert telemetry.snapshot()[
                "mmlspark_telemetry_retained_traces"]["series"][0][
                    "value"] >= 1

            # --- aggregate reconciliation: phases partition each request
            snap = telemetry.snapshot()
            fam = snap["mmlspark_serving_phase_seconds"]
            assert {s["labels"]["phase"]
                    for s in fam["series"]} == set(PHASES)
            # every request left every stage: nine stamps a phase
            assert {s["labels"]["phase"]: s["count"]
                    for s in fam["series"]} == dict.fromkeys(PHASES, 9)
            phase_sum = sum(s["sum"] for s in fam["series"])
            req = snap["mmlspark_http_request_seconds"]["series"][0]
            assert req["count"] == 9
            # the ledger covers admission -> reply encoded; the request
            # histogram adds only the reply-write syscall on top
            assert phase_sum <= req["sum"] * 1.001
            # dispatch/batch-wait are phase VIEWS of the same ledger:
            # never more than the phases they are cut from
            disp = snap["mmlspark_serving_dispatch_seconds"]["series"][0]
            tail_phases = sum(s["sum"] for s in fam["series"]
                              if s["labels"]["phase"] in
                              ("pad", "device", "readback", "reply"))
            assert disp["count"] >= 2
            assert disp["sum"] <= tail_phases + 1e-6
            wait = snap["mmlspark_serving_batch_wait_seconds"]["series"][0]
            head_phases = sum(s["sum"] for s in fam["series"]
                              if s["labels"]["phase"] in ("queue", "form"))
            assert wait["count"] >= 2
            assert wait["sum"] <= head_phases + 1e-6

            # --- each retained trace's phase spans partition its request
            # span (ts/dur are floored microseconds: a boundary may read
            # one short of the next span's start)
            for t in tids:
                evs = telemetry.trace.retained_events(t)
                req_ev = next(e for e in evs
                              if e["name"] == "serve/request")
                phase_evs = sorted((e for e in evs
                                    if e["name"] == "serve/phase"),
                                   key=lambda e: e["args"]["seq"])
                assert [e["args"]["phase"]
                        for e in phase_evs] == list(PHASES)
                at = req_ev["ts"]           # admission
                for e in phase_evs:
                    assert 0 <= e["ts"] - at <= 1, (t, e)
                    at = e["ts"] + e["dur"]
                # the reply stamp precedes the request span's end
                assert at <= req_ev["ts"] + req_ev["dur"] + 1

            # --- exemplar: the retained id on the bucket it landed in
            text = telemetry.registry.prometheus_text()
            assert ' # {trace_id="' in text
            assert tid in text

            # --- the trace is fetchable where the exemplar points
            code, doc = _get_json(f"{source.url}debug/trace/{tid}")
            assert code == 200 and doc["trace_id"] == tid
            names = {e["name"] for e in doc["events"]}
            assert {"serve/request", "serve/phase"} <= names
            assert all((e.get("args") or {}).get("trace_id") == tid
                       for e in doc["events"])
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"{source.url}debug/trace/deadbeef", timeout=5)
            assert ei.value.code == 404
        finally:
            loop.stop()
            source.close()


# ------------------------------------------------------------ tail sampling

class TestTailSampling:
    def _traced_event(self, tracer):
        ctx = tracectx.new_trace()
        tracer.complete("serve/request", time.perf_counter_ns() - 1000,
                        parent=ctx)
        return ctx.trace_id

    def test_retained_trace_survives_ring_overflow_burst(self, tel):
        small = telemetry.Tracer(max_events=8)
        small.enable_tail_sampling(quantile=0.99, min_samples=30)
        tid = self._traced_event(small)
        assert small.tail_complete(tid, latency_s=0.5, flagged=True)
        # bury the ring: 100 untraced events into an 8-slot deque
        t0 = time.perf_counter_ns()
        for _ in range(100):
            small.complete("noise", t0)
        assert small.dropped() >= 92
        # the pinned store is not the ring: the retained trace survives
        assert small.is_retained(tid)
        assert small.retained_ids() == [tid]
        evs = small.retained_events(tid)
        assert [e["name"] for e in evs] == ["serve/request"]
        assert any((e.get("args") or {}).get("trace_id") == tid
                   for e in small.events())

    def test_healthy_trace_dropped_and_counted(self, tel):
        tr = telemetry.Tracer()
        tr.enable_tail_sampling(quantile=0.99, min_samples=30)
        before = _counter_total("mmlspark_telemetry_tail_dropped")
        tid = self._traced_event(tr)
        # warmup window (threshold unknown), no error/shed/flag: dropped
        assert tr.tail_complete(tid, latency_s=0.001) is False
        assert _counter_total("mmlspark_telemetry_tail_dropped") \
            == before + 1
        assert not tr.is_retained(tid)
        assert [e["name"] for e in tr.events()] == ["clock/anchor"]

    def test_slow_quantile_verdict(self, tel):
        tr = telemetry.Tracer()
        tr.enable_tail_sampling(quantile=0.5, min_samples=4)
        for v in (0.01, 0.02, 0.03, 0.04):   # seed the latency window
            tr.tail_complete(tracectx.new_trace().trace_id, latency_s=v)
        slow = self._traced_event(tr)
        assert tr.tail_complete(slow, latency_s=1.0) is True
        fast = self._traced_event(tr)
        assert tr.tail_complete(fast, latency_s=0.001) is False
        assert tr.retained_ids() == [slow]

    def test_error_shed_flag_verdicts_ignore_threshold(self, tel):
        tr = telemetry.Tracer()
        tr.enable_tail_sampling()
        for kw in ({"error": True}, {"shed": True}, {"flagged": True}):
            tid = self._traced_event(tr)
            assert tr.tail_complete(tid, latency_s=0.001, **kw)
        assert len(tr.retained_ids()) == 3

    def test_ttl_expiry_unpins(self, tel):
        tr = telemetry.Tracer()
        tr.enable_tail_sampling(ttl=0.05)
        tid = self._traced_event(tr)
        assert tr.tail_complete(tid, error=True)
        time.sleep(0.1)
        # expiry runs on the next verdict delivery
        tr.tail_complete(tracectx.new_trace().trace_id, latency_s=0.01)
        assert not tr.is_retained(tid)
        assert tr.retained_ids() == []

    def test_export_unpin_semantics(self, tel, tmp_path):
        tr = telemetry.Tracer()
        tr.enable_tail_sampling()
        tid = self._traced_event(tr)
        assert tr.tail_complete(tid, error=True)
        # the read-only path (debug endpoints): export keeps the pin
        p1 = str(tmp_path / "a.jsonl")
        tr.export_chrome_trace(p1, unpin=False)
        assert tid in open(p1).read()
        assert tr.is_retained(tid)
        # the delivery path: export unpins
        p2 = str(tmp_path / "b.jsonl")
        tr.export_chrome_trace(p2)
        assert tid in open(p2).read()
        assert not tr.is_retained(tid)


# -------------------------------------------------------------- exemplars

class TestExemplars:
    def test_exposition_syntax_and_absence_when_never_retained(self, tel):
        h = telemetry.registry.histogram("test_attr_seconds", "syntax pin",
                                         buckets=(0.1, 1.0))
        h.observe(0.05)
        assert " # {" not in telemetry.registry.prometheus_text()
        h.observe(0.3, exemplar="0af7651916cd43dd8448eb211c80319c")
        text = telemetry.registry.prometheus_text()
        assert ('test_attr_seconds_bucket{le="1"} 2 # {trace_id='
                '"0af7651916cd43dd8448eb211c80319c"} 0.3') in text
        # the untouched bucket stays plain
        assert 'test_attr_seconds_bucket{le="0.1"} 1\n' in text
        # exemplar=None is the not-retained observe: no attachment
        h.observe(0.05, exemplar=None)
        assert telemetry.registry.prometheus_text().count(" # {") == 1

    def test_exemplar_survives_federation_merge_with_worker_label(self,
                                                                  tel):
        h = telemetry.registry.histogram("test_attr_fed_seconds",
                                         "merge pin", buckets=(0.1, 1.0))
        h.observe(0.3, exemplar="feedc0de")
        s = TimeSeriesSampler(interval=1.0)
        s.tick(now=T0)
        snap = s.snapshot()
        key = 'test_attr_fed_seconds_bucket{le="1"}'
        assert snap["exemplars"][key]["trace_id"] == "feedc0de"
        assert snap["exemplars"][key]["value"] == pytest.approx(0.3)

        fed = FederatedSampler(interval=1.0)
        fed.merge(now=T0)
        fed.ingest("w0", snap, now=T0 + 1)
        fed.merge(now=T0 + 1)
        text = fed.prometheus_text(now=T0 + 1)
        # fleet aggregate: exemplar gains the worker that observed it
        assert (' # {trace_id="feedc0de",worker="w0"} 0.3'
                in text)
        # worker child series: worker identity is in the key already
        assert 'test_attr_fed_seconds_bucket{le="1",worker="w0"}' in text
        # a worker that never retained contributes no exemplars
        fed2 = FederatedSampler(interval=1.0)
        fed2.merge(now=T0)
        plain = dict(snap, series=dict(snap["series"]))
        plain.pop("exemplars")
        fed2.ingest("w1", plain, now=T0 + 1)
        fed2.merge(now=T0 + 1)
        assert " # {" not in fed2.prometheus_text(now=T0 + 1)

    def test_forget_worker_drops_its_exemplars(self, tel):
        h = telemetry.registry.histogram("test_attr_forget_seconds", "",
                                         buckets=(1.0,))
        h.observe(0.3, exemplar="aaaa")
        s = TimeSeriesSampler(interval=1.0)
        s.tick(now=T0)
        fed = FederatedSampler(interval=1.0)
        fed.merge(now=T0)
        fed.ingest("w0", s.snapshot(), now=T0 + 1)
        fed.forget_worker("w0", absorb=True)
        fed.merge(now=T0 + 1)
        assert "aaaa" not in fed.prometheus_text(now=T0 + 1)


# ------------------------------------------------- /debug/trace endpoints

class TestDebugTraceEndpoints:
    def test_worker_control_port_serves_trace_and_404s(self, tel):
        from mmlspark_tpu.io.http.worker import WorkerServer
        w = WorkerServer("127.0.0.1")
        try:
            ctx = tracectx.new_trace()
            telemetry.trace.complete("serve/request",
                                     time.perf_counter_ns() - 1000,
                                     parent=ctx)
            base = f"http://127.0.0.1:{w.control_port}/debug/trace"
            code, doc = _get_json(f"{base}/{ctx.trace_id}")
            assert code == 200 and doc["trace_id"] == ctx.trace_id
            assert doc["events"] and "pid" in doc
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{base}/deadbeef", timeout=5)
            assert ei.value.code == 404
        finally:
            w.close()

    def test_driver_debug_trace_merges_and_counts_failures(self, tel):
        """The driver's cross-worker fetch: collects every live worker's
        spans plus its own, merges by trace id, keeps retained traces
        pinned (read-only path), answers None for unknown ids, and
        counts workers whose trace fetch failed."""
        from mmlspark_tpu.io.http.fleet import ProcessHTTPSource, _Worker
        from mmlspark_tpu.io.http.worker import WorkerServer
        ws = WorkerServer("127.0.0.1")
        dead = _Worker("127.0.0.1", 1, 1, spawn=False)
        handle = _Worker("127.0.0.1", ws.source.port, ws.control_port,
                         spawn=False)
        src = ProcessHTTPSource(workers=[handle, dead])
        try:
            telemetry.trace.enable_tail_sampling()
            ctx = tracectx.new_trace()
            telemetry.trace.complete("serve/request",
                                     time.perf_counter_ns() - 1000,
                                     parent=ctx)
            assert telemetry.trace.tail_complete(ctx.trace_id, error=True)
            before = _counter_total("mmlspark_fleet_trace_collect_failures")
            evs = src.debug_trace(ctx.trace_id)
            assert evs
            assert all((e.get("args") or {}).get("trace_id")
                       == ctx.trace_id
                       for e in evs if e.get("ph") != "M")
            # read-only: the debug fetch must not unpin the trace
            assert telemetry.trace.is_retained(ctx.trace_id)
            assert src.debug_trace("deadbeef") is None
            # the dead worker failed collection in both calls, counted
            assert _counter_total(
                "mmlspark_fleet_trace_collect_failures") == before + 2
        finally:
            try:
                src.close()
            except Exception:
                pass
            ws.close()

    def test_driver_http_endpoint_uses_fleet_trace_hook(self, tel):
        src = HTTPSource(name="attr-debug")
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{src.url}debug/trace/none",
                                       timeout=5)
            assert ei.value.code == 404
            src.fleet_trace = lambda tid: (
                [{"name": "serve/request", "ph": "X",
                  "args": {"trace_id": tid}}] if tid == "abc" else None)
            code, doc = _get_json(f"{src.url}debug/trace/abc")
            assert code == 200
            assert doc["events"][0]["args"]["trace_id"] == "abc"
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{src.url}debug/trace/xyz",
                                       timeout=5)
            assert ei.value.code == 404
        finally:
            src.close()


# ------------------------------------------------------------- bench doc

class TestAttributionBench:
    def test_open_loop_doc_carries_attribution_metrics(self, tel):
        """The --open-loop bench emits the phase breakdown and the
        attribution-overhead comparison into its mmlspark-bench/v1
        doc."""
        import bench_serving
        doc = bench_serving.open_loop_main(
            rate=120.0, duration=0.6, pool=16, smoke=True,
            max_wait=0.002, engines=("continuous",))
        assert doc["schema"] == "mmlspark-bench/v1"
        names = {m["metric"] for m in doc["metrics"]}
        assert "serving_open_loop_goodput_rps" in names
        # phase breakdown: queue and device percentiles at minimum
        assert "serving_open_loop_phase_queue_p50_ms" in names
        assert "serving_open_loop_phase_device_p50_ms" in names
        assert "serving_open_loop_phase_sum_ratio" in names
        ratio = next(m for m in doc["metrics"]
                     if m["metric"] == "serving_open_loop_phase_sum_ratio")
        assert 0.5 < ratio["value"] <= 1.001
        ov = next(m for m in doc["metrics"]
                  if m["metric"]
                  == "serving_open_loop_attribution_overhead_pct")
        assert ov["budget_pct"] == 2.0 and isinstance(ov["ok"], bool)
        assert "serving_open_loop_exemplar_linked" in names
        assert "serving_open_loop_trace_fetch_ok" in names
