"""Continuous-batching serving engine (io/serving): bucket policy, batch
formation (max-wait deadline, padding, carry-over), the fused
decode->pad->pjit->unpad step, AOT executable bundles (round trip, torn
fallback, warm restart with zero compiles), SLO-driven admission shed,
and the `serving.batch` / `serving.bundle_load` chaos sites."""

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
from mmlspark_tpu.io.http.server import HTTPSource, _Exchange
from mmlspark_tpu.io.serving import (BucketPolicy, ContinuousBatcher,
                                     ContinuousServingLoop,
                                     FusedServingStep, load_bundle,
                                     pow2_bucket, save_bundle,
                                     serve_continuous)
from mmlspark_tpu.models.modules import build_model
from mmlspark_tpu.resilience import faults
from mmlspark_tpu.resilience.ckpt import CorruptCheckpoint


@pytest.fixture
def tel():
    telemetry.enable()
    telemetry.registry.reset()
    yield telemetry
    telemetry.disable()


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


def _mk_step(params, max_batch=32, output="argmax"):
    return FusedServingStep(_CFG, params,
                            policy=BucketPolicy(max_batch=max_batch,
                                                min_bucket=8),
                            row_shape=_ROW, in_dtype=np.float32,
                            output=output)


def _payload(row: np.ndarray) -> bytes:
    return base64.b64encode(np.asarray(row, np.float32).tobytes())


def _post(url, data: bytes, timeout=30.0):
    req = urllib.request.Request(url, data=data)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read().decode()


# ------------------------------------------------------------ bucket policy

class TestBucketPolicy:
    def test_pow2_buckets_and_selection(self):
        pol = BucketPolicy(max_batch=64, min_bucket=8)
        assert pol.buckets == [8, 16, 32, 64]
        assert pol.bucket_for(1) == 8
        assert pol.bucket_for(8) == 8
        assert pol.bucket_for(9) == 16
        assert pol.bucket_for(33) == 64
        assert pol.bucket_for(64) == 64

    def test_non_pow2_bounds_round_up(self):
        pol = BucketPolicy(max_batch=100, min_bucket=5)
        assert pol.min_bucket == 8 and pol.max_batch == 128
        assert pol.buckets == [8, 16, 32, 64, 128]

    def test_oversized_batch_rejected(self):
        pol = BucketPolicy(max_batch=32)
        with pytest.raises(ValueError, match="exceed max_batch"):
            pol.bucket_for(33)

    def test_invalid_bounds(self):
        with pytest.raises(ValueError):
            BucketPolicy(max_batch=4, min_bucket=8)

    def test_pow2_bucket_helper(self):
        assert pow2_bucket(0) == 8
        assert pow2_bucket(100, lo=8, hi=64) == 64   # hi caps


# ------------------------------------------------------- batch formation

class _FakeSource:
    """source.drain-compatible test double over a deque of exchanges."""

    def __init__(self):
        self.items = []
        self.replies = {}
        self._lock = threading.Lock()

    def add(self, value):
        ex = _Exchange(str(value))
        with self._lock:
            self.items.append(ex)
        return ex

    def drain(self, max_rows, timeout=0.05, wait_first=True):
        deadline = time.monotonic() + timeout
        while True:
            with self._lock:
                take, self.items = (self.items[:max_rows],
                                    self.items[max_rows:])
            if take or not wait_first:
                return take
            if time.monotonic() >= deadline:
                return []
            time.sleep(0.002)

    def respond(self, ex_id, code, body):
        self.replies[ex_id] = (code, body)


class TestContinuousBatcher:
    def test_partial_batch_waits_then_pads_to_bucket(self, tel):
        src = _FakeSource()
        b = ContinuousBatcher(src, BucketPolicy(max_batch=32),
                              max_wait=0.05)
        for i in range(5):
            src.add(i)
        t0 = time.perf_counter()
        exchanges, bucket = b.next_batch()
        waited = time.perf_counter() - t0
        assert [ex.value for ex in exchanges] == ["0", "1", "2", "3", "4"]
        assert bucket == 8               # 5 rows -> padded 8-bucket
        # the max-wait deadline was honored: the lone batch waited for
        # more rows but no longer than max_wait (+ scheduling slack)
        assert 0.02 <= waited < 0.5
        snap = telemetry.snapshot()
        assert snap["mmlspark_serving_pad_waste"]["series"][0][
            "value"] == pytest.approx(3 / 8)

    def test_full_bucket_dispatches_without_deadline(self):
        src = _FakeSource()
        b = ContinuousBatcher(src, BucketPolicy(max_batch=16),
                              max_wait=5.0)   # would be visible if waited
        for i in range(16):
            src.add(i)
        t0 = time.perf_counter()
        exchanges, bucket = b.next_batch()
        assert (len(exchanges), bucket) == (16, 16)
        assert time.perf_counter() - t0 < 1.0   # no max_wait stall

    def test_overflow_stays_queued_in_arrival_order(self):
        src = _FakeSource()
        b = ContinuousBatcher(src, BucketPolicy(max_batch=16),
                              max_wait=0.01)
        for i in range(20):
            src.add(i)
        first, bucket1 = b.next_batch()
        assert [ex.value for ex in first] == [str(i) for i in range(16)]
        # the 4 deferred rows keep their ORIGINAL arrival stamps, so the
        # next batch's deadline is already expired: immediate dispatch
        t0 = time.perf_counter()
        second, bucket2 = b.next_batch()
        assert [ex.value for ex in second] == ["16", "17", "18", "19"]
        assert (bucket1, bucket2) == (16, 8)
        assert time.perf_counter() - t0 < 0.5

    def test_idle_returns_none(self):
        src = _FakeSource()
        b = ContinuousBatcher(src, BucketPolicy(max_batch=16),
                              max_wait=0.01, idle_timeout=0.02)
        assert b.next_batch() is None


# ------------------------------------------------------------- fused step

class TestFusedServingStep:
    def test_padding_correct_and_matches_direct_apply(self, tiny_params):
        step = _mk_step(tiny_params, output="scores")
        rng = np.random.default_rng(0)
        rows = rng.normal(size=(5,) + _ROW).astype(np.float32)
        out = step.score_rows(rows, 8)
        module = build_model(_CFG)
        ref = np.asarray(module.apply(tiny_params, rows))
        assert out.shape == ref.shape            # padding sliced off
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    def test_compile_buckets_then_all_warm(self, tel, tiny_params):
        step = _mk_step(tiny_params)
        assert step.warm_buckets() == []
        n = step.compile_buckets()
        assert n == 3 and step.warm_buckets() == [8, 16, 32]
        assert step.compiles() == 3
        assert step.compile_buckets() == 0       # idempotent
        assert _counter_total("mmlspark_serving_aot_compiles_total") == 3

    def test_cache_hit_miss_accounting(self, tel, tiny_params):
        step = _mk_step(tiny_params)
        rows = np.zeros((3,) + _ROW, np.float32)
        step.score_rows(rows, 8)                 # cold: live-traffic miss
        assert _counter_total(
            "mmlspark_serving_exec_cache_misses_total") == 1
        step.score_rows(rows, 8)                 # now warm
        assert _counter_total(
            "mmlspark_serving_exec_cache_hits_total") == 1

    def test_decode_round_trip_and_errors(self, tiny_params):
        step = _mk_step(tiny_params)
        row = np.arange(6, dtype=np.float32)
        np.testing.assert_array_equal(
            step.decode(_payload(row).decode()), row)
        with pytest.raises(ValueError, match="expected 6"):
            step.decode(base64.b64encode(b"\x00" * 8).decode())

    def test_output_validation(self, tiny_params):
        with pytest.raises(ValueError, match="argmax|scores"):
            _mk_step(tiny_params, output="probabilities")


# ------------------------------------------------- end-to-end serving loop

class TestServeContinuous:
    def test_requests_batched_and_answered(self, tel, tiny_params):
        step = _mk_step(tiny_params)
        source, loop = serve_continuous(step, max_wait=0.01)
        rng = np.random.default_rng(1)
        try:
            results = {}

            def client(i):
                row = rng.normal(size=_ROW).astype(np.float32)
                results[i] = (_post(source.url, _payload(row)), row)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(12)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            assert len(results) == 12
            module = build_model(_CFG)
            for i, ((code, body), row) in results.items():
                assert code == 200
                ref = int(np.argmax(np.asarray(
                    module.apply(tiny_params, row[None]))[0]))
                assert json.loads(body)["label"] == ref, i
            # every dispatch went through a policy bucket, pre-compiled:
            # live traffic never compiled
            assert _counter_total(
                "mmlspark_serving_exec_cache_misses_total") == 0
            hist = telemetry.snapshot()["mmlspark_serving_bucket_rows"]
            assert sum(s["count"] for s in hist["series"]) >= 1
        finally:
            loop.stop()
            source.close()

    def test_bad_payload_answers_400_alone(self, tel, tiny_params):
        step = _mk_step(tiny_params)
        source, loop = serve_continuous(step, max_wait=0.01)
        try:
            good = _payload(np.zeros(_ROW, np.float32))
            ok = {}
            t = threading.Thread(
                target=lambda: ok.update(r=_post(source.url, good)))
            t.start()
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(source.url, base64.b64encode(b"\x01\x02"))
            assert ei.value.code == 400
            t.join(timeout=30)
            assert ok["r"][0] == 200     # its bucket-mate still answered
        finally:
            loop.stop()
            source.close()

    def test_slo_breach_sheds_at_admission(self, tel, tiny_params):
        """Deterministic shed under injected burn: a shed_on_breach
        error-rate objective breaches -> the NEXT request is rejected
        503 + Retry-After at admission, before it enters the batch
        queue."""
        from mmlspark_tpu.telemetry.registry import MetricsRegistry
        from mmlspark_tpu.telemetry.slo import SLOEngine
        from mmlspark_tpu.telemetry.timeseries import TimeSeriesSampler
        reg = MetricsRegistry()
        ts = TimeSeriesSampler(registry=reg)
        eng = SLOEngine([{
            "name": "errors", "kind": "error_rate",
            "bad": "t_cb_bad_total", "total": "t_cb_requests_total",
            "target": 0.9, "windows": [10, 60],
            "shed_on_breach": True}], sampler=ts)
        total = reg.counter("t_cb_requests", "")
        bad = reg.counter("t_cb_bad", "")
        step = _mk_step(tiny_params)
        source, loop = serve_continuous(step, max_wait=0.01, slo=eng)
        try:
            payload = _payload(np.zeros(_ROW, np.float32))
            assert _post(source.url, payload)[0] == 200
            # inject the burn: 90% of traffic failing across both windows
            total.inc(10); bad.inc(9)
            ts.tick(now=0.0)
            total.inc(10); bad.inc(9)
            ts.tick(now=5.0)
            eng.evaluate(now=5.0)
            assert eng.should_shed()
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(source.url, payload)
            assert ei.value.code == 503
            assert ei.value.headers["Retry-After"] is not None
            # snapshot keys are REGISTERED names (exposition adds _total)
            assert _counter_total("mmlspark_http_shed_requests") >= 1
            # budget recovers -> admission reopens
            eng.evaluate(now=1e4)
            assert _post(source.url, payload)[0] == 200
        finally:
            loop.stop()
            source.close()

    @pytest.mark.chaos
    def test_chaos_serving_batch_site_retries_transient(self, tel,
                                                        tiny_params):
        """One-shot chaos at `serving.batch`: the first dispatch raises
        an InjectedFault; the loop's RetryPolicy replays the SAME bucket
        and the client still gets its 200."""
        faults.configure("serving.batch:error:1.0:0:1", seed=0)
        step = _mk_step(tiny_params)
        source, loop = serve_continuous(step, max_wait=0.01)
        try:
            code, body = _post(source.url,
                               _payload(np.zeros(_ROW, np.float32)))
            assert code == 200
            assert _counter_total("mmlspark_faults_injected_total") == 1
        finally:
            loop.stop()
            source.close()
            faults.clear()


# ------------------------------------------------------------ AOT bundles

class TestBundle:
    def test_round_trip_restores_warm_executables(self, tel, tiny_params,
                                                  tmp_path):
        step = _mk_step(tiny_params, output="scores")
        save_bundle(str(tmp_path), step)
        assert (tmp_path / "serving_bundle.json").exists()
        assert (tmp_path / "manifest.json").exists()
        loaded = load_bundle(str(tmp_path))
        # every bucket warm, ZERO compiles in the loaded step
        assert loaded.warm_buckets() == step.policy.buckets
        assert loaded.compiles() == 0
        rows = np.random.default_rng(2).normal(
            size=(3,) + _ROW).astype(np.float32)
        np.testing.assert_allclose(loaded.score_rows(rows, 8),
                                   step.score_rows(rows, 8),
                                   rtol=1e-6, atol=1e-6)
        assert loaded.compiles() == 0            # scoring stayed warm
        snap = telemetry.snapshot()
        series = snap["mmlspark_serving_bundle_loads_total"]["series"]
        # other outcomes' children may exist at 0 from earlier tests
        # (reset zeroes cells in place, it does not drop children)
        assert {tuple(sorted(s["labels"].items())): s["value"]
                for s in series if s["value"]} == {(("result", "warm"),): 1.0}

    def test_torn_exec_shard_falls_back_to_cold_compile(self, tel,
                                                        tiny_params,
                                                        tmp_path):
        step = _mk_step(tiny_params)
        save_bundle(str(tmp_path), step)
        # tear ONE executable shard (truncate past the manifest commit)
        shard = tmp_path / "bundle_exec_b16.bin"
        shard.write_bytes(shard.read_bytes()[:-7])
        loaded = load_bundle(str(tmp_path))
        assert loaded.warm_buckets() == [8, 32]  # 16 lost its warmth
        assert _counter_total(
            "mmlspark_serving_bundle_exec_failures_total") == 1
        # the torn bucket still SERVES — one counted cold compile
        out = loaded.score_rows(np.zeros((10,) + _ROW, np.float32), 16)
        assert out.shape == (10,)
        assert loaded.compiles() == 1
        assert _counter_total(
            "mmlspark_serving_exec_cache_misses_total") == 1

    def test_torn_model_shard_is_fatal(self, tel, tiny_params, tmp_path):
        step = _mk_step(tiny_params)
        save_bundle(str(tmp_path), step)
        blob = (tmp_path / "bundle_model.msgpack").read_bytes()
        (tmp_path / "bundle_model.msgpack").write_bytes(blob[:-3])
        with pytest.raises(CorruptCheckpoint):
            load_bundle(str(tmp_path))

    def test_absent_bundle_raises(self, tel, tmp_path):
        with pytest.raises(FileNotFoundError):
            load_bundle(str(tmp_path))
        series = telemetry.snapshot()[
            "mmlspark_serving_bundle_loads_total"]["series"]
        assert series[0]["labels"]["result"] == "absent"

    @pytest.mark.chaos
    def test_chaos_bundle_load_site_degrades_to_cold(self, tel,
                                                     tiny_params,
                                                     tmp_path):
        """One-shot chaos at `serving.bundle_load`: an injected fault on
        the first bucket's executable load degrades THAT bucket to a
        cold compile (counted); the rest load warm and the worker comes
        up serving."""
        step = _mk_step(tiny_params)
        save_bundle(str(tmp_path), step)
        faults.configure("serving.bundle_load:error:1.0:0:1", seed=0)
        try:
            loaded = load_bundle(str(tmp_path))
        finally:
            faults.clear()
        assert loaded.warm_buckets() == [16, 32]
        assert _counter_total(
            "mmlspark_serving_bundle_exec_failures_total") == 1
        assert loaded.score_rows(
            np.zeros((2,) + _ROW, np.float32), 8).shape == (2,)


# --------------------------------------- warm restart under open-loop load

class TestWarmRestart:
    @pytest.mark.chaos
    def test_worker_killed_under_load_restarts_warm(self, tel,
                                                    tiny_params,
                                                    tmp_path):
        """THE warm-start guarantee: kill a self-serving bundle worker
        under open-loop load; the supervisor restarts it from the same
        bundle and the fresh incarnation answers with ZERO new XLA
        compiles (recompile counters flat across the restart)."""
        from mmlspark_tpu.io.http.fleet import (ProcessHTTPSource,
                                                _Worker)
        from mmlspark_tpu.io.http.worker import WorkerServer
        from mmlspark_tpu.resilience.policy import RetryPolicy
        from mmlspark_tpu.resilience.supervisor import FleetSupervisor

        step = _mk_step(tiny_params)
        save_bundle(str(tmp_path), step)
        servers = [WorkerServer("127.0.0.1", bundle=str(tmp_path))]
        handle = _Worker("127.0.0.1", servers[0].source.port,
                         servers[0].control_port, spawn=False)
        src = ProcessHTTPSource(workers=[handle])
        assert servers[0].step.compiles() == 0   # came up warm

        def respawn(wi, old):
            ws = WorkerServer(old.host, port=old.port,
                              control_port=old.control,
                              bundle=str(tmp_path))
            servers.append(ws)
            return _Worker(old.host, ws.source.port, ws.control_port,
                           spawn=False)

        sup = FleetSupervisor(src, probe_interval=0.05,
                              probe_timeout=0.5, restart_backoff=0.05,
                              respawn=respawn).start()
        url = f"http://127.0.0.1:{servers[0].source.port}/"
        payload = _payload(np.zeros(_ROW, np.float32))
        stop = threading.Event()
        outcomes = []

        def client():
            policy = RetryPolicy(name="test.cb.client", max_attempts=60,
                                 base_delay=0.05, max_delay=0.3,
                                 deadline=30.0, seed=1)
            while not stop.is_set():
                outcomes.append(policy.run(
                    lambda _a: _post(url, payload, timeout=3.0)))
                time.sleep(0.01)

        threads = [threading.Thread(target=client) for _ in range(3)]
        # snapshot keys are registered names (no _total here)
        compiles_before = _counter_total(
            "mmlspark_profiler_compiles")
        assert compiles_before >= 3     # the bundle build compiled
        try:
            for t in threads:
                t.start()
            time.sleep(0.3)                  # open-loop traffic flowing
            servers[0].close()               # kill the worker mid-load
            deadline = time.monotonic() + 30
            while len(servers) < 2 and time.monotonic() < deadline:
                time.sleep(0.05)
            assert len(servers) >= 2, "supervisor never restarted"
            time.sleep(0.4)                  # traffic against the fresh one
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=30)
            sup.stop()
            for ws in servers[1:]:
                ws.close()
            src.close()
        assert outcomes and all(c == 200 for c, _ in outcomes)
        # the restarted incarnation loaded the bundle: zero compiles in
        # its step AND the process-wide compile counter stayed flat
        assert servers[-1].step.compiles() == 0
        assert _counter_total(
            "mmlspark_profiler_compiles") == compiles_before
        assert _counter_total(
            "mmlspark_serving_exec_cache_misses_total") == 0


# ------------------------------------------------------- open-loop bench

class TestOpenLoopBench:
    def test_arrival_schedules_deterministic(self):
        import bench_serving
        a = bench_serving.arrival_times("poisson", 100.0, 2.0, seed=3)
        b = bench_serving.arrival_times("poisson", 100.0, 2.0, seed=3)
        np.testing.assert_array_equal(a, b)
        assert ((a > 0) & (a < 2.0)).all()
        assert 100 < len(a) < 320        # ~rate * duration
        bu = bench_serving.arrival_times("bursty", 100.0, 2.0, seed=3)
        assert ((bu >= 0) & (bu < 2.0)).all()
        # bursty: arrivals confined to the duty windows of each period
        phase = bu % 1.0
        assert (phase <= 0.25 + 1e-9).all()
        with pytest.raises(ValueError, match="poisson|bursty"):
            bench_serving.arrival_times("adversarial", 1.0, 1.0)
