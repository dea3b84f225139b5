"""Resilience subsystem: retry/breaker policies, deterministic fault
injection, and chaos tests driving the serving fleet + trainer recovery
paths on CPU (fast, seeded, tier-1 — the ``chaos`` marker).

The fleet chaos tests run the worker servers IN-PROCESS (WorkerServer +
spawn=False handles) so a kill/restart cycle costs milliseconds, not a
subprocess jax import; the real-subprocess fleet lives in
test_serving_fleet.py's extended tier.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from mmlspark_tpu import telemetry
from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.core.utils import object_column
from mmlspark_tpu.io.http.fleet import ProcessHTTPSource, ReplayServingLoop, \
    _Worker
from mmlspark_tpu.io.http.worker import WorkerServer
from mmlspark_tpu.resilience import faults
from mmlspark_tpu.resilience.policy import (BreakerOpen, CircuitBreaker,
                                            RetryPolicy, default_transient)
from mmlspark_tpu.resilience.supervisor import FleetSupervisor


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture
def telemetry_on():
    telemetry.enable()
    telemetry.registry.reset()
    yield telemetry
    telemetry.disable()


# --------------------------------------------------------------- policies

class TestRetryPolicy:
    def test_succeeds_after_transient_failures(self):
        sleeps = []
        p = RetryPolicy(max_attempts=4, base_delay=0.1, seed=0,
                        sleep=sleeps.append)
        calls = []

        def fn(attempt):
            calls.append(attempt)
            if attempt < 2:
                raise ConnectionError("blip")
            return "ok"

        assert p.run(fn) == "ok"
        assert calls == [0, 1, 2]
        assert len(sleeps) == 2

    def test_fatal_errors_not_retried(self):
        p = RetryPolicy(max_attempts=5, base_delay=0.0)
        calls = []

        def fn(attempt):
            calls.append(attempt)
            raise ValueError("bad input")

        with pytest.raises(ValueError):
            p.run(fn)
        assert calls == [0]

    def test_budget_exhaustion_raises_last_error(self):
        p = RetryPolicy(max_attempts=3, base_delay=0.0)
        with pytest.raises(TimeoutError):
            p.run(lambda a: (_ for _ in ()).throw(TimeoutError(str(a))))

    def test_full_jitter_bounds(self):
        p = RetryPolicy(base_delay=0.1, multiplier=2.0, max_delay=0.5,
                        seed=7)
        for attempt in range(8):
            cap = min(0.5, 0.1 * 2 ** attempt)
            for _ in range(20):
                assert 0.0 <= p.backoff(attempt) <= cap

    def test_deadline_budget(self):
        # base_delay 10s >> deadline: the first retry would blow the
        # budget, so the policy gives up immediately without sleeping
        sleeps = []
        p = RetryPolicy(max_attempts=10, base_delay=10.0, multiplier=1.0,
                        max_delay=10.0, deadline=0.05, seed=1,
                        sleep=sleeps.append)
        t0 = time.monotonic()
        with pytest.raises(ConnectionError):
            p.run(lambda a: (_ for _ in ()).throw(ConnectionError()))
        assert time.monotonic() - t0 < 1.0
        assert not sleeps

    def test_default_classification(self):
        assert default_transient(ConnectionError())
        assert default_transient(TimeoutError())
        assert default_transient(urllib.error.URLError("x"))
        assert default_transient(faults.InjectedFault("s"))
        assert not default_transient(ValueError())
        assert not default_transient(KeyError())
        err = ValueError("tagged")
        err.transient = True
        assert default_transient(err)
        http500 = urllib.error.HTTPError("u", 500, "boom", {}, None)
        http404 = urllib.error.HTTPError("u", 404, "gone", {}, None)
        assert default_transient(http500)
        assert not default_transient(http404)

    def test_retry_metrics(self, telemetry_on):
        p = RetryPolicy(name="t.metrics", max_attempts=2, base_delay=0.0)
        with pytest.raises(ConnectionError):
            p.run(lambda a: (_ for _ in ()).throw(ConnectionError()))
        snap = telemetry.snapshot()
        series = {tuple(s["labels"].items()): s["value"]
                  for s in snap["mmlspark_retry_attempts_total"]["series"]}
        assert series[(("policy", "t.metrics"),)] == 1
        series = {tuple(s["labels"].items()): s["value"]
                  for s in snap["mmlspark_retry_exhausted_total"]["series"]}
        assert series[(("policy", "t.metrics"),)] == 1


class TestCircuitBreaker:
    def _clock(self):
        t = {"now": 0.0}

        def clock():
            return t["now"]
        return t, clock

    def test_state_machine(self):
        t, clock = self._clock()
        b = CircuitBreaker("test.sm", failure_threshold=2,
                           reset_timeout=1.0, clock=clock)
        assert b.allow("w") and b.state("w") == "closed"
        b.record("w", ok=False)
        assert b.state("w") == "closed"     # one failure: still closed
        b.record("w", ok=False)
        assert b.state("w") == "open"       # threshold reached
        assert not b.allow("w")             # short-circuited
        t["now"] = 1.5                      # reset window elapsed
        assert b.allow("w")                 # half-open probe admitted
        assert b.state("w") == "half_open"
        assert not b.allow("w")             # only one probe in flight
        b.record("w", ok=True)
        assert b.state("w") == "closed"     # probe success closes

    def test_half_open_failure_reopens(self):
        t, clock = self._clock()
        b = CircuitBreaker("test.ho", failure_threshold=1,
                           reset_timeout=1.0, clock=clock)
        b.record("w", ok=False)
        t["now"] = 1.1
        assert b.allow("w")
        b.record("w", ok=False)
        assert b.state("w") == "open"
        assert not b.allow("w")

    def test_call_wrapper_and_targets_independent(self):
        b = CircuitBreaker("test.call", failure_threshold=1,
                           reset_timeout=60.0)
        with pytest.raises(RuntimeError):
            b.call(lambda: (_ for _ in ()).throw(RuntimeError()), "a")
        with pytest.raises(BreakerOpen):
            b.call(lambda: "x", "a")
        assert b.call(lambda: "fine", "b") == "fine"   # target b unharmed
        b.reset("a")
        assert b.call(lambda: "back", "a") == "back"

    def test_snapshot_all(self):
        b = CircuitBreaker("test.snap", failure_threshold=1)
        b.record("t0", ok=False)
        snap = CircuitBreaker.snapshot_all()
        assert snap["test.snap"]["t0"] == "open"


# --------------------------------------------------------- fault injection

class TestFaultInjection:
    def test_spec_parsing_and_validation(self):
        assert faults.parse("a.b:error:0.5") == [("a.b", "error", 0.5, [])]
        assert faults.parse("a:delay:1.0:0.02 ; b:error:0.1:3:2") == [
            ("a", "delay", 1.0, ["0.02"]), ("b", "error", 0.1, ["3", "2"])]
        with pytest.raises(ValueError):
            faults.parse("missing-fields")
        with pytest.raises(ValueError):
            faults.configure("a:explode:0.5")
        with pytest.raises(ValueError):
            faults.configure("a:error:1.5")

    def test_off_by_default_and_clear(self):
        assert not faults.active()
        faults.inject("anything")           # no-op, no error
        faults.configure("x:error:1.0")
        with pytest.raises(faults.InjectedFault):
            faults.inject("x")
        faults.clear()
        faults.inject("x")                  # disarmed again

    def test_seeded_determinism(self):
        def pattern():
            faults.configure("d.site:error:0.3", seed=42)
            hits = []
            for _ in range(100):
                try:
                    faults.inject("d.site")
                    hits.append(0)
                except faults.InjectedFault:
                    hits.append(1)
            return hits

        a, b = pattern(), pattern()
        assert a == b                       # same seed -> same pattern
        assert 10 < sum(a) < 60             # ~30% of 100
        faults.configure("d.site:error:0.3", seed=43)
        c = [0] * 100
        for i in range(100):
            try:
                faults.inject("d.site")
            except faults.InjectedFault:
                c[i] = 1
        assert c != a                       # different seed -> different

    def test_error_after_and_budget_args(self):
        faults.configure("t:error:1.0:2:1")    # arm after 2 calls, 1 total
        faults.inject("t")
        faults.inject("t")                     # 2 clean warmup calls
        with pytest.raises(faults.InjectedFault):
            faults.inject("t")
        faults.inject("t")                     # budget spent: clean again

    def test_delay_kind_sleeps(self):
        faults.configure("slow:delay:1.0:0.02")
        t0 = time.perf_counter()
        faults.inject("slow")
        assert time.perf_counter() - t0 >= 0.02

    def test_env_gating(self, monkeypatch):
        monkeypatch.setenv("MMLSPARK_TPU_FAULTS", "e.site:error:1.0")
        monkeypatch.setenv("MMLSPARK_TPU_FAULTS_SEED", "9")
        faults._init_from_env()
        assert faults.active()
        with pytest.raises(faults.InjectedFault):
            faults.inject("e.site")

    def test_injected_counter(self, telemetry_on):
        faults.configure("m.site:error:1.0")
        with pytest.raises(faults.InjectedFault):
            faults.inject("m.site")
        snap = telemetry.snapshot()["mmlspark_faults_injected_total"]
        assert any(s["labels"] == {"site": "m.site", "kind": "error"}
                   and s["value"] == 1 for s in snap["series"])


# ------------------------------------------------------- serving: healthz

class _Echo:
    def transform(self, df: DataFrame) -> DataFrame:
        replies = object_column(
            [json.dumps({"echo": v}) for v in df.col("value")])
        return df.withColumn("reply", replies)


def _post(url, payload, timeout=10.0):
    req = urllib.request.Request(url, data=payload.encode(),
                                 headers={"Content-Type": "text/plain"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read().decode()


def _get_json(url, timeout=5.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def test_healthz_on_serving_server():
    from mmlspark_tpu.io.http import serve_pipeline
    source, loop = serve_pipeline(_Echo())
    try:
        code, h = _get_json(source.url.rstrip("/") + "/healthz")
        assert code == 200 and h["ok"] is True
        assert h["queue_depth"] == 0
        assert h["uptime_s"] >= 0
        assert isinstance(h["breakers"], dict)
    finally:
        loop.stop()
        source.close()


def test_healthz_on_worker_control_plane():
    w = WorkerServer("127.0.0.1")
    try:
        code, h = _get_json(f"http://127.0.0.1:{w.control_port}/healthz")
        assert code == 200 and h["ok"] is True
        assert h["unacked"] == 0 and h["queue_depth"] == 0
        assert h["port"] == w.source.port
        # the public port answers the same probe
        code, h2 = _get_json(f"http://127.0.0.1:{w.source.port}/healthz")
        assert code == 200 and h2["ok"] is True
    finally:
        w.close()


def test_load_shedding_503_with_retry_after(telemetry_on):
    from mmlspark_tpu.io.http.server import HTTPSource
    src = HTTPSource(max_queue_depth=1)
    results = {}
    try:
        t = threading.Thread(target=lambda: results.update(
            first=_post(src.url, "held", timeout=15)))
        t.start()
        deadline = time.monotonic() + 5
        while src._n_pending < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert src._n_pending == 1
        # queue full: the next request is shed immediately
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(src.url, "shed-me", timeout=5)
        assert ei.value.code == 503
        assert ei.value.headers["Retry-After"] == "1"
        _, h = _get_json(src.url.rstrip("/") + "/healthz")
        assert h["queue_depth"] == 1 and h["max_queue_depth"] == 1
        # drain + reply: the held client completes normally
        batch = src.getBatch(max_rows=4, timeout=1.0)
        assert batch.count() == 1
        src.respond(str(batch.col("id")[0]), 200, "done")
        t.join(timeout=10)
        assert results["first"][0] == 200
        snap = telemetry.snapshot()["mmlspark_http_shed_requests"]
        assert snap["series"][0]["value"] >= 1
    finally:
        src.close()


# ----------------------------------------------- fleet chaos (in-process)

def _inproc_fleet(n_workers: int):
    """A real ProcessHTTPSource over IN-PROCESS WorkerServers: the full
    control protocol (poll/ack/respond/healthz) without subprocess spawn
    cost. Returns (servers, handles, source)."""
    servers, handles = [], []
    for _ in range(n_workers):
        ws = WorkerServer("127.0.0.1")
        servers.append(ws)
        handles.append(_Worker("127.0.0.1", ws.source.port,
                               ws.control_port, spawn=False))
    return servers, ProcessHTTPSource(workers=handles)


def _client_post(url, payload, deadline=30.0):
    """A resilient client: retries transport errors / 5xx with backoff —
    the contract chaos recovery relies on (a killed worker's clients see a
    fast transport error and retry against the restarted URL)."""
    policy = RetryPolicy(name="test.client", max_attempts=100,
                         base_delay=0.05, max_delay=0.3, deadline=deadline,
                         seed=0)
    return policy.run(lambda _a: _post(url, payload, timeout=3.0))


@pytest.mark.chaos
def test_fleet_chaos_poll_faults_and_worker_kill(telemetry_on):
    """The acceptance scenario: 10% injected poll errors plus one mid-run
    worker kill. Every client request is answered exactly once with the
    right body, the supervisor restarts the dead worker on its original
    port, and retry/breaker/restart metrics land in the snapshot."""
    faults.configure("fleet.poll:error:0.1", seed=0)
    servers, src = _inproc_fleet(2)
    ports = [w.port for w in src.workers]

    def respawn(wi, old):
        ws = WorkerServer(old.host, port=old.port, control_port=old.control)
        servers.append(ws)
        return _Worker(old.host, ws.source.port, ws.control_port,
                       spawn=False)

    sup = FleetSupervisor(src, probe_interval=0.05, probe_timeout=0.5,
                          restart_backoff=0.05, respawn=respawn).start()
    loop = ReplayServingLoop(src, _Echo(), supervisor=sup).start()
    results: dict = {}
    try:
        def client(i):
            url = f"http://127.0.0.1:{ports[i % 2]}/"
            try:
                results[i] = _client_post(url, f"chaos-{i}")
            except Exception as e:       # surfaced in the assert below
                results[i] = ("error", repr(e))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(12)]
        for t in threads[:6]:
            t.start()
        time.sleep(0.3)                  # traffic flowing through faults
        servers[0].close()               # hard-kill worker 0 mid-run
        for t in threads[6:]:
            t.start()
        for t in threads:
            t.join(timeout=40)
        assert len(results) == 12
        for i, (code, body) in results.items():
            assert code == 200, (i, code, body)
            assert json.loads(body)["echo"] == f"chaos-{i}", (i, body)
        # the supervisor restarted worker 0 on its original port
        assert src.workers[0].port == ports[0]
        assert src.aliveCount() == 2
        snap = telemetry.snapshot()
        restarts = sum(
            s["value"] for s in
            snap["mmlspark_supervisor_worker_restarts_total"]["series"])
        assert restarts >= 1
        injected = sum(
            s["value"] for s in
            snap["mmlspark_faults_injected_total"]["series"]
            if s["labels"].get("site") == "fleet.poll")
        assert injected >= 1
        assert "mmlspark_breaker_state" in snap
        assert "mmlspark_retry_attempts_total" in snap
    finally:
        loop.stop()                      # also stops the supervisor
        for ws in servers:
            try:
                ws.close()
            except Exception:
                pass


@pytest.mark.chaos
def test_fleet_transform_fault_replays_batch(telemetry_on):
    """An injected dispatch fault fails the first transform attempt; the
    replay contract re-reads the same offset range and the clients never
    see it."""
    faults.configure("fleet.transform:error:1.0:0:1", seed=0)  # first call
    servers, src = _inproc_fleet(1)
    loop = ReplayServingLoop(src, _Echo()).start()
    try:
        code, body = _client_post(src.workers[0].url, "replayed")
        assert code == 200 and json.loads(body)["echo"] == "replayed"
        snap = telemetry.snapshot()["mmlspark_faults_injected_total"]
        assert any(s["labels"].get("site") == "fleet.transform"
                   for s in snap["series"])
    finally:
        loop.stop()
        for ws in servers:
            ws.close()


@pytest.mark.chaos
def test_spurious_death_verdict_resurrection(telemetry_on):
    """The stranded-exchange fix: rows polled from a worker that got a
    WRONG death verdict used to be dropped (their clients hung until
    reply_timeout). Now they are parked, the supervisor's probe finds the
    worker alive, and restoreWorker returns them to the offset log — the
    blocked client gets its reply in milliseconds, not 30s."""
    servers, src = _inproc_fleet(1)
    sup = FleetSupervisor(src, probe_timeout=0.5)   # tick()ed manually
    got: dict = {}
    try:
        t = threading.Thread(target=lambda: got.update(
            r=_post(src.workers[0].url, "stranded?", timeout=20)))
        t.start()
        start = src.committedOffset()
        deadline = time.monotonic() + 10
        end = start
        while end == start and time.monotonic() < deadline:
            end = src.getOffset()           # row enters the offset log
        assert end > start
        src.markWorkerDead(0, reason="simulated spurious verdict")
        assert src.getBatch(start, end).count() == 0   # parked, not lost
        sup.tick()                          # probe: alive -> resurrect
        assert src.workers[0].alive
        end2 = src._offset
        batch = src.getBatch(start, end2)   # redispatched under new offset
        assert batch.col("value").tolist() == ["stranded?"]
        out = _Echo().transform(batch)
        for i in range(out.count()):
            src.respond(str(out.col("id")[i]), 200, str(out.col("reply")[i]))
        src.flush()
        src.commit(end2)
        t.join(timeout=10)
        assert got["r"][0] == 200
        assert json.loads(got["r"][1])["echo"] == "stranded?"
        snap = telemetry.snapshot()
        assert snap["mmlspark_fleet_rows_parked"]["series"][0]["value"] == 1
        assert snap["mmlspark_fleet_rows_redispatched"]["series"][0][
            "value"] == 1
    finally:
        for ws in servers:
            ws.close()


@pytest.mark.chaos
def test_reply_delivery_retries_transient_respond_fault(telemetry_on):
    """The seed DROPPED computed replies when one /respond round-trip
    failed transiently (clients hung until reply_timeout). The shared
    RetryPolicy now retries delivery within the flush."""
    faults.configure("fleet.respond:error:1.0:0:1", seed=0)   # first call
    servers, src = _inproc_fleet(1)
    loop = ReplayServingLoop(src, _Echo()).start()
    try:
        t0 = time.monotonic()
        code, body = _client_post(src.workers[0].url, "deliver-me")
        assert code == 200 and json.loads(body)["echo"] == "deliver-me"
        # delivered by the in-flush retry, NOT by a 30s reply_timeout 504
        assert time.monotonic() - t0 < 10
    finally:
        loop.stop()
        for ws in servers:
            ws.close()


# ------------------------------------------------------- trainer recovery

def _toy_learner(ck: str):
    from mmlspark_tpu.models.trainer import TpuLearner
    return (TpuLearner()
            .setModelConfig({"type": "mlp", "hidden": [4],
                             "num_classes": 2})
            .setEpochs(1).setBatchSize(8).setLearningRate(0.05)
            .setDeviceDataCap(1)            # force the per-step feed path
            .setCheckpointDir(ck).setCheckpointEverySteps(2))


def _toy_df(n=64):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int64)
    return DataFrame({"features": object_column([r for r in x]),
                      "label": y})


@pytest.mark.chaos
def test_trainer_kill_and_resume_from_step_checkpoint(tmp_path,
                                                      telemetry_on):
    """Preemption tolerance: a fit killed mid-epoch (armed trainer.step
    fault that outlives the retry-once budget) leaves step-interval
    checkpoints; the refit resumes from the last one and only runs the
    remaining steps."""
    ck = str(tmp_path / "ck")
    df = _toy_df(64)                      # 64 rows / bs 8 -> 8 steps
    faults.configure("trainer.step:error:1.0:5", seed=0)  # die at step 5
    with pytest.raises(ConnectionError):
        _toy_learner(ck).fit(df)
    names = sorted(os.listdir(ck))
    assert "ckpt_00000_s0000003.msgpack" in names       # steps 1 and 3
    assert "ckpt_00000.msgpack" not in names            # epoch incomplete
    faults.clear()

    telemetry.registry.reset()
    learner = _toy_learner(ck)
    assert learner._latest_checkpoint() == (0, 3)
    model = learner.fit(df)
    assert np.isfinite(model._final_loss)
    # resumed at step 4: exactly 4 of the 8 steps dispatched in the refit
    step_hist = telemetry.snapshot()["mmlspark_trainer_step_seconds"]
    assert step_hist["series"][0]["count"] == 4
    # the epoch-final checkpoint pruned its step checkpoints (the
    # manifest rides along — it vouches for the survivor)
    names = sorted(os.listdir(ck))
    assert names == ["ckpt_00000.msgpack", "manifest.json"]
    assert learner._latest_checkpoint() == (0, None)


@pytest.mark.chaos
def test_trainer_step_retry_absorbs_single_fault(telemetry_on, tmp_path):
    """One transient step fault costs a retry, not the fit: with a fault
    budget of 1 the retry-once policy completes training."""
    faults.configure("trainer.step:error:1.0:2:1", seed=0)
    model = _toy_learner(str(tmp_path / "ck")).fit(_toy_df(32))
    assert np.isfinite(model._final_loss)
    snap = telemetry.snapshot()
    retried = sum(s["value"]
                  for s in snap["mmlspark_retry_attempts_total"]["series"]
                  if s["labels"].get("policy") == "trainer.step")
    assert retried == 1


def test_checkpoint_name_parsing():
    from mmlspark_tpu.models.trainer import TpuLearner
    parse = TpuLearner._parse_ckpt_name
    assert parse("ckpt_00002.msgpack") == (2, None)
    assert parse("ckpt_00002_s0000005.msgpack") == (2, 5)
    assert parse("ckpt_00002.msgpack.tmp.0") is None
    assert parse("other.msgpack") is None
    # epoch-final outranks same-epoch steps; later steps outrank earlier
    learner = TpuLearner().setCheckpointDir("")
    assert learner._latest_checkpoint() is None


# ------------------------------------------------------- elastic training

def _elastic_learner(ck: str, epochs: int = 1):
    from mmlspark_tpu.models.trainer import TpuLearner
    return (TpuLearner()
            .setModelConfig({"type": "mlp", "hidden": [4],
                             "num_classes": 2})
            .setEpochs(epochs).setBatchSize(8).setLearningRate(0.05)
            .setDeviceDataCap(1)            # force the per-step feed path
            .setCheckpointDir(ck).setCheckpointEverySteps(2))


class TestTrainSupervisor:
    """Deterministic (tick-driven, injected-probe) verdict machinery."""

    def test_grace_window_and_sticky_verdict(self, tmp_path):
        from mmlspark_tpu.resilience.elastic import TrainSupervisor
        ages = {"host0": 0.0, "host1": 0.0}
        sup = TrainSupervisor(["host0", "host1"], str(tmp_path),
                              grace=1.0, probe=ages.get)
        sup.tick()
        assert sup.dead_hosts() == set()
        ages["host1"] = 5.0
        sup.tick()
        assert sup.dead_hosts() == {"host1"}
        assert sup.alive_hosts() == ["host0"]
        # a zombie heartbeat resuming does NOT resurrect: its devices left
        # the mesh, rejoining means relaunching
        ages["host1"] = 0.0
        sup.tick()
        assert sup.dead_hosts() == {"host1"}

    def test_missing_heartbeat_fatal_after_grace(self, tmp_path):
        from mmlspark_tpu.resilience.elastic import TrainSupervisor
        sup = TrainSupervisor(["host0"], str(tmp_path), grace=0.05,
                              probe=lambda h: None)
        sup.tick()                       # inside the startup grace: alive
        assert sup.dead_hosts() == set()
        time.sleep(0.08)
        sup.tick()
        assert sup.dead_hosts() == {"host0"}

    def test_shrink_vs_restart_decision(self, tmp_path):
        from mmlspark_tpu.resilience.elastic import TrainSupervisor
        ages = {f"host{i}": 0.0 for i in range(3)}
        sup = TrainSupervisor(list(ages), str(tmp_path), grace=1.0,
                              min_hosts=2, probe=ages.get)
        assert sup.decision() == "shrink"
        ages["host0"] = 9.0
        sup.tick()
        assert sup.decision() == "shrink"    # 2 alive == min_hosts
        ages["host1"] = 9.0
        sup.tick()
        assert sup.decision() == "restart"   # 1 alive < min_hosts

    def test_heartbeat_file_roundtrip(self, tmp_path):
        from mmlspark_tpu.resilience.elastic import (HostHeartbeat,
                                                     TrainSupervisor)
        hb = HostHeartbeat("hostX", str(tmp_path), interval=0.02).start()
        try:
            hb.beat(1, 7)
            sup = TrainSupervisor(["hostX"], str(tmp_path), grace=5.0)
            time.sleep(0.06)
            age = sup._probe_file("hostX")
            assert age is not None and age < 1.0
            doc = json.load(open(hb.path))
            assert doc["host"] == "hostX"
            assert (doc["epoch"], doc["step"]) == (1, 7)
        finally:
            hb.stop()

    def test_heartbeat_probe_fault_site(self, tmp_path, telemetry_on):
        from mmlspark_tpu.resilience.elastic import TrainSupervisor
        faults.configure("supervisor.heartbeat:error:1.0", seed=0)
        sup = TrainSupervisor(["host0"], str(tmp_path), grace=1.0,
                              probe=lambda h: 0.0)
        with pytest.raises(ConnectionError):
            sup.tick()


def test_elastic_requires_checkpoint_dir():
    from mmlspark_tpu.models.trainer import TpuLearner
    from mmlspark_tpu.resilience.elastic import ElasticFitCoordinator
    with pytest.raises(ValueError, match="checkpointDir"):
        ElasticFitCoordinator(TpuLearner())


def test_elastic_rejects_inner_axes(tmp_path):
    from mmlspark_tpu.models.trainer import TpuLearner
    learner = (_elastic_learner(str(tmp_path / "ck"))
               .setElastic(True).setPipelineParallel(2)
               .setModelConfig({"type": "transformer", "vocab_size": 8,
                                "d_model": 8, "heads": 2, "layers": 2,
                                "num_classes": 2}))
    with pytest.raises(ValueError, match="elastic"):
        learner.fit(_toy_df(16))


def test_elastic_fleet_lost_below_min_hosts(tmp_path):
    """Survivors < min_hosts: the coordinator refuses in-job recovery and
    points at the checkpointDir relaunch path."""
    from mmlspark_tpu.resilience.elastic import (ElasticFitCoordinator,
                                                 ElasticFleetLost)
    coord = ElasticFitCoordinator(_elastic_learner(str(tmp_path / "ck")),
                                  n_hosts=2, min_hosts=2, grace=60.0)
    coord.supervisor._dead.add("host1")
    with pytest.raises(ElasticFleetLost, match="min_hosts"):
        coord._remesh({"host1"})


@pytest.mark.chaos
def test_elastic_fit_clean_run_no_overhead_path(tmp_path, telemetry_on):
    """No faults, no deaths: the elastic wrapper is pass-through — one
    attempt, every step committed once, no remesh."""
    model = (_elastic_learner(str(tmp_path / "ck"))
             .setElastic(True).setElasticHosts(4)
             .setElasticGraceSeconds(5.0)).fit(_toy_df(64))
    assert np.isfinite(model._final_loss)
    snap = telemetry.snapshot()
    assert snap["mmlspark_elastic_remeshes_total"]["series"][0]["value"] == 0
    assert snap["mmlspark_elastic_hosts_alive"]["series"][0]["value"] == 4


@pytest.mark.chaos
def test_elastic_fit_survives_host_kill(tmp_path, telemetry_on):
    """THE elastic guarantee: an in-process "host" killed mid-fit under a
    10% step-fault rate is detected by heartbeat silence, the fit
    re-meshes over the survivors and resumes from the consensus
    checkpoint bit-exactly — every one of the epoch's steps is committed
    (replays allowed, losses not), and the fit returns a model without a
    refit."""
    from flax import serialization
    from mmlspark_tpu.models.trainer import _params_digest
    from mmlspark_tpu.resilience.elastic import ElasticFitCoordinator

    ck = str(tmp_path / "ck")
    df = _toy_df(64)                      # 64 rows / bs 8 -> 8 steps
    learner = _elastic_learner(ck)
    # 10% elastic.step faults (absorbed by the step retry) + a per-step
    # delay so the fit outlives the verdict path
    faults.configure("elastic.step:error:0.1;trainer.step:delay:1.0:0.1",
                     seed=3)
    coord = ElasticFitCoordinator(learner, n_hosts=4, grace=0.3,
                                  heartbeat_interval=0.05)

    ckpt_copies = {}
    done = threading.Event()

    def watch_and_kill():
        # keep a copy of every checkpoint file (the epoch-final save
        # prunes step checkpoints) and kill host2's heartbeat as soon as
        # the first step checkpoint lands
        killed = False
        while not done.is_set():
            for f in os.listdir(ck) if os.path.isdir(ck) else []:
                if f.startswith("ckpt_") and f.endswith(".msgpack") \
                        and f not in ckpt_copies:
                    try:
                        ckpt_copies[f] = open(os.path.join(ck, f),
                                              "rb").read()
                    except OSError:
                        continue    # pruned between listdir and open
                    if not killed and "_s" in f:
                        coord.heartbeats["host2"].kill()
                        killed = True
            time.sleep(0.005)

    t = threading.Thread(target=watch_and_kill, daemon=True)
    t.start()
    try:
        model = coord.fit(df)
    finally:
        done.set()
        t.join(timeout=5)
    assert np.isfinite(model._final_loss)

    # recovery happened: host2 dead, exactly one re-mesh onto 6 devices
    assert coord.supervisor.dead_hosts() == {"host2"}
    assert len(coord.attempts) >= 2
    final = coord.attempts[-1]
    assert final["hosts"] == ["host0", "host1", "host3"]
    assert final["devices"] == 6
    snap = telemetry.snapshot()
    assert snap["mmlspark_elastic_remeshes_total"]["series"][0]["value"] \
        >= 1
    losses = snap["mmlspark_elastic_host_losses_total"]["series"]
    assert [s["labels"]["host"] for s in losses if s["value"] > 0] \
        == ["host2"]

    # zero lost committed steps: every step of the epoch was committed
    # (the steps after the consensus checkpoint are replayed, never
    # skipped)
    assert {s for (_e, s) in coord.committed} == set(range(8))

    # bit-exact resume: the resumed attempt's restored params digest
    # equals the digest of the checkpoint file it resumed from
    epoch, step = final["resume_pos"]
    name = f"ckpt_{epoch:05d}_s{step:07d}.msgpack"
    assert name in ckpt_copies, (name, sorted(ckpt_copies))
    state = serialization.msgpack_restore(ckpt_copies[name])
    assert _params_digest(state["params"]) == final["resume_digest"]
    assert final.get("recovery_s", 0) > 0

    # the epoch-final checkpoint pruned its step checkpoints
    assert sorted(f for f in os.listdir(ck) if f.endswith(".msgpack")) \
        == ["ckpt_00000.msgpack"]


# ------------------------------------- async checkpoints + commit protocol

class TestAsyncCheckpointWriter:
    """resilience/ckpt.py: depth-1 newest-wins queue, wait barrier,
    manifest-last commit protocol."""

    def test_publish_commits_manifest_last(self, tmp_path):
        from mmlspark_tpu.resilience import ckpt
        d = str(tmp_path)
        ckpt.publish(os.path.join(d, "ckpt_00000.msgpack"), b"x" * 64)
        files = ckpt.load_manifest(d)
        assert files["ckpt_00000.msgpack"]["size"] == 64
        assert ckpt.verify(d, "ckpt_00000.msgpack")

    def test_newest_wins_coalescing(self, tmp_path, telemetry_on):
        from mmlspark_tpu.resilience.ckpt import AsyncCheckpointWriter
        d = str(tmp_path)
        written = []

        def slow_payload(tag):
            def fn():
                time.sleep(0.15)
                written.append(tag)
                return tag.encode()
            return fn

        w = AsyncCheckpointWriter("t")
        try:
            # first starts immediately; 2 and 3 land while it is in
            # flight -> 2 is coalesced away, 3 survives
            w.submit(os.path.join(d, "ckpt_00001.msgpack"),
                     slow_payload("one"))
            time.sleep(0.03)          # let the worker pick up "one"
            w.submit(os.path.join(d, "ckpt_00002.msgpack"),
                     slow_payload("two"))
            w.submit(os.path.join(d, "ckpt_00003.msgpack"),
                     slow_payload("three"))
            assert w.wait(timeout=10)
        finally:
            w.close()
        assert written == ["one", "three"]
        names = sorted(f for f in os.listdir(d) if f.endswith(".msgpack"))
        assert names == ["ckpt_00001.msgpack", "ckpt_00003.msgpack"]
        snap = telemetry.snapshot()
        assert snap["mmlspark_ckpt_coalesced_total"]["series"][0]["value"] \
            == 1

    def test_writer_error_surfaces_at_wait(self, tmp_path):
        from mmlspark_tpu.resilience.ckpt import AsyncCheckpointWriter
        faults.configure("ckpt.write:error:1.0", seed=0)
        w = AsyncCheckpointWriter("t")
        try:
            w.submit(str(tmp_path / "ckpt_00000.msgpack"), lambda: b"x")
            with pytest.raises(ConnectionError):
                w.wait(timeout=10)
        finally:
            faults.clear()
            w.close()
        # the failed write published nothing
        assert not (tmp_path / "ckpt_00000.msgpack").exists()

    @pytest.mark.chaos
    def test_crash_at_rename_leaves_no_candidate(self, tmp_path,
                                                 telemetry_on):
        """A fault at ckpt.rename (crash between write and publish):
        the final name never appears, the manifest is untouched, and the
        previous checkpoint remains the consensus candidate."""
        from mmlspark_tpu.resilience import ckpt
        d = str(tmp_path)
        ckpt.publish(os.path.join(d, "ckpt_00000_s0000001.msgpack"),
                     b"good")
        faults.configure("ckpt.rename:error:1.0", seed=0)
        try:
            with pytest.raises(ConnectionError):
                ckpt.publish(
                    os.path.join(d, "ckpt_00000_s0000003.msgpack"),
                    b"doomed")
        finally:
            faults.clear()
        assert not os.path.exists(
            os.path.join(d, "ckpt_00000_s0000003.msgpack"))
        assert "ckpt_00000_s0000003.msgpack" not in ckpt.load_manifest(d)
        assert ckpt.verify(d, "ckpt_00000_s0000001.msgpack")


@pytest.mark.chaos
def test_torn_checkpoint_skipped_at_resume(tmp_path, telemetry_on):
    """A ckpt file the manifest never vouched for (rename landed, crash
    before the manifest commit) must not become the consensus candidate:
    resume skips it, counts it corrupt, and falls back."""
    ck = str(tmp_path / "ck")
    df = _toy_df(32)                       # 4 steps -> ckpts at s1, s3
    faults.configure("trainer.step:error:1.0:3", seed=0)   # die at step 3
    with pytest.raises(ConnectionError):
        _toy_learner(ck).fit(df)
    faults.clear()
    learner = _toy_learner(ck)
    assert learner._latest_checkpoint() == (0, 1)
    # forge a NEWER checkpoint that skipped the manifest commit
    with open(os.path.join(ck, "ckpt_00000_s0000003.msgpack"), "wb") as f:
        f.write(b"torn garbage")
    assert learner._latest_checkpoint() == (0, 1)     # skipped, not picked
    snap = telemetry.snapshot()
    assert snap["mmlspark_ckpt_corrupt_total"]["series"][0]["value"] >= 1
    # and the refit trains through from the good checkpoint
    model = learner.fit(df)
    assert np.isfinite(model._final_loss)


@pytest.mark.chaos
def test_corrupt_checkpoint_content_falls_back(tmp_path, telemetry_on):
    """Manifest-listed but content-corrupt (bit rot / truncation after
    commit): the sha check at restore time rejects it and the resume
    falls back to the previous checkpoint instead of crashing."""
    ck = str(tmp_path / "ck")
    df = _toy_df(32)
    faults.configure("trainer.step:error:1.0:3", seed=0)
    with pytest.raises(ConnectionError):
        _toy_learner(ck).fit(df)
    faults.clear()
    # corrupt the newest checkpoint IN PLACE, fixing up the manifest size
    # so only the content hash can catch it
    from mmlspark_tpu.resilience import ckpt as ckptlib
    name = "ckpt_00000_s0000001.msgpack"
    size = os.path.getsize(os.path.join(ck, name))
    with open(os.path.join(ck, name), "wb") as f:
        f.write(b"\xff" * size)
    learner = _toy_learner(ck)
    assert learner._latest_checkpoint() == (0, 1)   # size still matches
    model = learner.fit(df)                         # sha rejects -> fresh
    assert np.isfinite(model._final_loss)
    snap = telemetry.snapshot()
    assert snap["mmlspark_ckpt_corrupt_total"]["series"][0]["value"] >= 1


def test_step_checkpoint_retention_keep_last_k(tmp_path):
    """checkpointKeepSteps bounds a long fit's step-ckpt accumulation:
    only the newest K survive as new ones commit."""
    ck = str(tmp_path / "ck")
    df = _toy_df(128)                      # 16 steps, ckpt every 2
    faults.configure("trainer.step:error:1.0:14", seed=0)  # die at s14
    with pytest.raises(ConnectionError):
        _toy_learner(ck).fit(df)           # keep default: 3
    faults.clear()
    steps = sorted(f for f in os.listdir(ck)
                   if f.endswith(".msgpack") and "_s" in f)
    assert steps == ["ckpt_00000_s%07d.msgpack" % s for s in (9, 11, 13)]
    # and the retained set resumes fine
    model = _toy_learner(ck).fit(df)
    assert np.isfinite(model._final_loss)


@pytest.mark.chaos
def test_async_checkpoint_kill_and_resume(tmp_path, telemetry_on):
    """asyncCheckpoint=True preserves the kill-and-resume contract: the
    background-published checkpoints are manifest-verified and the refit
    resumes from the newest committed one."""
    ck = str(tmp_path / "ck")
    df = _toy_df(64)
    faults.configure("trainer.step:error:1.0:5", seed=0)
    with pytest.raises(ConnectionError):
        _toy_learner(ck).setAsyncCheckpoint(True).fit(df)
    faults.clear()
    learner = _toy_learner(ck).setAsyncCheckpoint(True)
    pos = learner._latest_checkpoint()
    assert pos is not None and pos[1] is not None
    from mmlspark_tpu.resilience import ckpt as ckptlib
    assert ckptlib.load_manifest(ck)       # commits went through the protocol
    model = learner.fit(df)
    assert np.isfinite(model._final_loss)


# ---------------------------------------------- heartbeat hardening + grow

def test_heartbeat_write_retry_and_errors_counter(tmp_path, telemetry_on):
    """A shared-FS outage must not silently kill the beacon thread: the
    write retries, exhaustion is counted, and the beacon resumes once
    storage heals."""
    from mmlspark_tpu.resilience.elastic import HostHeartbeat
    d = str(tmp_path / "hb")
    hb = HostHeartbeat("hostX", d, interval=0.03).start()
    try:
        deadline = time.time() + 5
        while time.time() < deadline and not os.path.exists(hb.path):
            time.sleep(0.02)
        assert os.path.exists(hb.path)
        # simulate the outage: the directory becomes unwritable (a file
        # squats on its name). One rename takes it away: a walk that
        # unlinks entry by entry races the beacon's own tmp files
        os.rename(d, d + ".gone")
        with open(d, "w") as f:
            f.write("squatter")
        deadline = time.time() + 5
        snap = {}
        while time.time() < deadline:
            snap = telemetry.snapshot()
            series = snap.get("mmlspark_elastic_heartbeat_errors_total",
                              {}).get("series", [])
            if any(s["value"] > 0 for s in series):
                break
            time.sleep(0.02)
        series = snap["mmlspark_elastic_heartbeat_errors_total"]["series"]
        assert any(s["labels"]["host"] == "hostX" and s["value"] > 0
                   for s in series)
        assert hb._thread.is_alive()       # the beacon survived
        # storage heals -> beats resume
        os.remove(d)
        os.makedirs(d)
        deadline = time.time() + 5
        while time.time() < deadline and not os.path.exists(hb.path):
            time.sleep(0.02)
        assert os.path.exists(hb.path)
    finally:
        hb.stop()


def test_supervisor_clears_stale_heartbeats(tmp_path):
    """hb_*.json ghosts from a previous run must not produce instant
    verdicts on a reused checkpointDir. Staleness is judged by the
    file's MTIME (the filesystem's clock), never the dead writer's wall
    clock — a ghost from a skew-ahead host still clears."""
    from mmlspark_tpu.resilience.elastic import TrainSupervisor
    d = str(tmp_path)
    ghost = os.path.join(d, "hb_host0.json")
    with open(ghost, "w") as f:
        # a skewed writer stamped a FUTURE wall time; only the mtime
        # tells the truth
        json.dump({"host": "host0", "time": time.time() + 3600,
                   "epoch": 4, "step": 9}, f)
    os.utime(ghost, (time.time() - 3600, time.time() - 3600))
    fresh = {"host": "host1", "time": time.time(), "epoch": 0, "step": 0}
    with open(os.path.join(d, "hb_host1.json"), "w") as f:
        json.dump(fresh, f)
    sup = TrainSupervisor(["host0", "host1"], d, grace=60.0)
    sup.clear_stale_heartbeats()
    assert not os.path.exists(os.path.join(d, "hb_host0.json"))  # ghost
    assert os.path.exists(os.path.join(d, "hb_host1.json"))      # fresh
    sup.tick()          # missing file is inside the startup grace: alive
    assert sup.dead_hosts() == set()


class TestGrowVerdicts:
    """The death pass's mirror: joining heartbeats -> grow verdicts."""

    def _dead_sup(self, d, **kw):
        from mmlspark_tpu.resilience.elastic import TrainSupervisor
        sup = TrainSupervisor(["host0", "host1"], d, grace=1.0, **kw)
        sup._dead.add("host1")
        return sup

    def _write_hb(self, d, host, joining, age=0.0):
        with open(os.path.join(d, f"hb_{host}.json"), "w") as f:
            json.dump({"host": host, "time": time.time() - age,
                       "epoch": 0, "step": 0,
                       **({"joining": True} if joining else {})}, f)

    def test_flagless_zombie_stays_dead(self, tmp_path):
        d = str(tmp_path)
        sup = self._dead_sup(d, rejoin_grace=0.0)
        self._write_hb(d, "host1", joining=False)    # zombie, no flag
        sup.tick()
        assert sup.joining_hosts() == {}
        assert sup.dead_hosts() == {"host1"}

    def test_joining_heartbeat_earns_grow_verdict(self, tmp_path):
        d = str(tmp_path)
        sup = self._dead_sup(d, rejoin_grace=0.0)
        self._write_hb(d, "host1", joining=True)
        sup.tick()
        assert set(sup.joining_hosts()) == {"host1"}
        # verdict is NOT an admit: still dead until the coordinator
        # admits at a checkpoint boundary
        assert sup.dead_hosts() == {"host1"}
        sup.admit("host1")
        assert sup.dead_hosts() == set()
        assert sup.joining_hosts() == {}

    def test_rejoin_grace_window(self, tmp_path):
        d = str(tmp_path)
        sup = self._dead_sup(d, rejoin_grace=0.2)
        self._write_hb(d, "host1", joining=True)
        sup.tick()
        assert sup.joining_hosts() == {}       # window not yet served
        time.sleep(0.25)
        self._write_hb(d, "host1", joining=True)   # still fresh
        sup.tick()
        assert set(sup.joining_hosts()) == {"host1"}

    def test_stale_joining_heartbeat_restarts_window(self, tmp_path):
        d = str(tmp_path)
        sup = self._dead_sup(d, rejoin_grace=0.2)
        self._write_hb(d, "host1", joining=True)
        sup.tick()
        time.sleep(0.25)
        self._write_hb(d, "host1", joining=True, age=5.0)   # went stale
        sup.tick()
        assert sup.joining_hosts() == {}       # flap: window restarted

    def test_rejoin_fault_site(self, tmp_path, telemetry_on):
        d = str(tmp_path)
        sup = self._dead_sup(d, rejoin_grace=0.0)
        self._write_hb(d, "host1", joining=True)
        faults.configure("supervisor.rejoin:error:1.0", seed=0)
        with pytest.raises(ConnectionError):
            sup._grow_pass()


@pytest.mark.chaos
def test_elastic_fit_grows_back_after_relaunch(tmp_path, telemetry_on):
    """THE grow guarantee: a host killed mid-fit shrinks the mesh; its
    relaunch (joining heartbeat) earns a grow verdict and the mesh grows
    back to full size at the next checkpoint boundary — no fleet
    restart, every step committed, replays only."""
    from mmlspark_tpu.resilience.elastic import ElasticFitCoordinator

    ck = str(tmp_path / "ck")
    df = _toy_df(64)                      # 8 steps/epoch
    learner = _elastic_learner(ck, epochs=2).setAsyncCheckpoint(True)
    faults.configure("trainer.step:delay:1.0:0.08", seed=3)  # pace the fit
    coord = ElasticFitCoordinator(learner, n_hosts=4, grace=0.3,
                                  heartbeat_interval=0.05,
                                  rejoin_grace=0.1)
    done = threading.Event()

    def chaos_script():
        # kill host2 at the first step checkpoint, relaunch it once the
        # shrink re-mesh is underway
        while not done.is_set():
            if os.path.isdir(ck) and any(
                    "_s" in f for f in os.listdir(ck)
                    if f.endswith(".msgpack")):
                coord.heartbeats["host2"].kill()
                break
            time.sleep(0.005)
        while not done.is_set():
            if len(coord.attempts) >= 2:
                coord.relaunch_host("host2")
                return
            time.sleep(0.005)

    t = threading.Thread(target=chaos_script, daemon=True)
    t.start()
    try:
        model = coord.fit(df)
    finally:
        done.set()
        t.join(timeout=5)
        faults.clear()
    assert np.isfinite(model._final_loss)

    # shrink happened, then grow: the final attempt runs on all 4 hosts
    # and host2 is alive again
    assert len(coord.attempts) >= 3
    assert coord.attempts[-1]["hosts"] == ["host0", "host1", "host2",
                                           "host3"]
    assert coord.attempts[-1]["devices"] == 8
    assert coord.supervisor.dead_hosts() == set()
    grow = next(a for a in coord.attempts if "grow_recovery_s" in a)
    assert grow["grow_recovery_s"] > 0
    snap = telemetry.snapshot()
    assert snap["mmlspark_elastic_grows_total"]["series"][0]["value"] >= 1
    rejoins = snap["mmlspark_elastic_rejoins_total"]["series"]
    assert [s["labels"]["host"] for s in rejoins if s["value"] > 0] \
        == ["host2"]
    # zero lost committed steps across both epochs (replays allowed)
    assert {(e, s) for (e, s) in coord.committed} \
        >= {(e, s) for e in range(2) for s in range(8)}


@pytest.mark.chaos
def test_elastic_max_hosts_caps_grow(tmp_path):
    """A joiner beyond elasticMaxHosts stays parked: pending_grow
    reports nobody while the pool is at the ceiling."""
    from mmlspark_tpu.resilience.elastic import ElasticFitCoordinator
    coord = ElasticFitCoordinator(_elastic_learner(str(tmp_path / "ck")),
                                  n_hosts=4, grace=60.0, max_hosts=3)
    coord.supervisor._dead.add("host3")
    coord._mesh_hosts = {"host0", "host1", "host2"}
    coord.supervisor._joining["host3"] = 0.0
    coord.note_checkpoint(0, 5)            # boundary committed
    assert coord.pending_grow() == set()   # at the cap: parked
    coord.max_hosts = 4
    assert coord.pending_grow() == {"host3"}


@pytest.mark.chaos
def test_elastic_fitstream_survives_host_kill(tmp_path, telemetry_on):
    """fitStream routed through the elastic coordinator: a host killed
    mid-stream re-meshes over the survivors and the fit completes (the
    interrupted epoch restarts from the checkpointed optimizer state)."""
    rng = np.random.default_rng(0)
    n = 64
    x = rng.normal(size=(n, 4)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int64)

    def batches():
        for i in range(0, n, 8):
            time.sleep(0.04)               # pace past the verdict window
            yield x[i:i + 8], y[i:i + 8]

    ck = str(tmp_path / "ck")
    learner = (_elastic_learner(ck, epochs=2)
               .setElastic(True).setElasticHosts(4)
               .setElasticGraceSeconds(0.3))
    coords = []
    orig = learner._elastic_coordinator

    def capture():
        c = orig()
        c._hb_interval = 0.05
        for h in c.heartbeats.values():
            h.interval = 0.05
        coords.append(c)
        return c

    learner._elastic_coordinator = capture
    done = threading.Event()

    def killer():
        while not done.is_set():
            if coords and len(coords[0].committed) >= 2:
                coords[0].heartbeats["host2"].kill()
                return
            time.sleep(0.005)

    t = threading.Thread(target=killer, daemon=True)
    t.start()
    try:
        model = learner.fitStream(lambda: batches())
    finally:
        done.set()
        t.join(timeout=5)
    assert np.isfinite(model._final_loss)
    coord = coords[0]
    assert coord.supervisor.dead_hosts() == {"host2"}
    assert len(coord.attempts) >= 2
    assert coord.attempts[-1]["hosts"] == ["host0", "host1", "host3"]
    snap = telemetry.snapshot()
    assert snap["mmlspark_elastic_remeshes_total"]["series"][0]["value"] \
        >= 1


@pytest.mark.chaos
def test_elastic_gbdt_kill_and_resume(tmp_path):
    """The boosting loop through ElasticStepContext: a host killed
    mid-fit re-meshes and the fit resumes from the per-iteration
    boosting snapshot — the full ensemble trains, trees built before the
    kill survive bit-exactly."""
    from mmlspark_tpu.models.gbdt.engine import (GBDTParams, fit_gbdt,
                                                 fit_gbdt_elastic)
    from mmlspark_tpu.resilience.elastic import ElasticFitCoordinator
    from mmlspark_tpu.parallel import mesh as meshlib

    rng = np.random.default_rng(0)
    n, d = 1024, 8
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    p = GBDTParams(num_iterations=10, max_depth=3, objective="binary",
                   tree_learner="data")
    ck = str(tmp_path / "ck")
    coord = ElasticFitCoordinator(checkpoint_dir=ck, n_hosts=4, grace=0.3,
                                  heartbeat_interval=0.05)
    # pace iterations so the kill lands mid-boosting
    faults.configure("elastic.step:delay:1.0:0.06", seed=0)
    done = threading.Event()

    def killer():
        while not done.is_set():
            if len(coord.committed) >= 2:      # >= 2 iterations done
                coord.heartbeats["host2"].kill()
                return
            time.sleep(0.005)

    t = threading.Thread(target=killer, daemon=True)
    t.start()

    def attempt(devices, ctx):
        mesh = meshlib.create_mesh(devices=devices)
        xp, n_real = meshlib.pad_batch_to_devices(x, mesh)
        yp = np.concatenate([y, np.zeros(len(xp) - n_real, y.dtype)])
        w = np.concatenate([np.ones(n_real, np.float32),
                            np.zeros(len(xp) - n_real, np.float32)])
        return fit_gbdt(xp, yp, p, mesh=mesh, sample_weight=w,
                        elastic_ctx=ctx)

    try:
        ens = coord.run(attempt)
    finally:
        done.set()
        t.join(timeout=5)
        faults.clear()
    assert coord.supervisor.dead_hosts() == {"host2"}
    assert len(coord.attempts) >= 2
    # the resumed attempt re-entered mid-boosting, not from scratch
    resumed = coord.attempts[-1]
    assert resumed["resume_pos"] is not None
    assert resumed["resume_pos"][1] >= 1
    # the full ensemble trained and the pre-kill trees survived
    # bit-exactly (the snapshot's prefix IS the final ensemble's prefix)
    assert ens.leaf.shape[0] == 10
    k = resumed["resume_pos"][1] + 1
    snap_leaves = coord.snapshot["leaves"][:k]
    for i in range(k):
        np.testing.assert_array_equal(np.asarray(ens.leaf)[i],
                                      np.asarray(snap_leaves[i]))
    from mmlspark_tpu.models.gbdt.engine import predict
    prob = predict(ens, x)
    pred = (prob[:, 1] if prob.ndim == 2 else prob) > 0.5
    assert (pred.astype(np.float32) == y).mean() > 0.8


@pytest.mark.chaos
def test_elastic_gbdt_stage_routing(tmp_path):
    """elasticConfig on the LightGBM stage routes the fit through the
    coordinator (clean run: pass-through, same-quality model)."""
    from mmlspark_tpu.models.gbdt.stages import LightGBMClassifier

    rng = np.random.default_rng(0)
    n = 9000                               # above the small-fit fallback
    x = rng.normal(size=(n, 6)).astype(np.float32)
    y = (x[:, 0] + x[:, 1] > 0).astype(np.float64)
    df = DataFrame({"features": object_column([r for r in x]),
                    "label": y})
    model = (LightGBMClassifier()
             .setNumIterations(5).setNumLeaves(4)
             .setElasticConfig({"checkpointDir": str(tmp_path / "ck"),
                                "hosts": 4, "graceSeconds": 5.0})
             .fit(df))
    out = model.transform(df)
    pred = np.asarray(out.col("prediction"))
    assert (pred == y).mean() > 0.8


# ------------------------------ seq heartbeats: clock-skew-proof verdicts

class TestSeqHeartbeats:
    """Death/grow freshness rides reader-observed seq advancement, not
    the writer's wall clock — one skewed host can neither be falsely
    killed nor kept as a ghost."""

    def _write(self, d, host, seq, wall_offset=0.0, joining=False):
        doc = {"host": host, "seq": seq, "time": time.time() + wall_offset,
               "epoch": 0, "step": seq}
        if joining:
            doc["joining"] = True
        with open(os.path.join(d, f"hb_{host}.json"), "w") as f:
            json.dump(doc, f)

    def test_skewed_wall_clock_does_not_kill_a_beating_host(self, tmp_path):
        from mmlspark_tpu.resilience.elastic import TrainSupervisor
        d = str(tmp_path)
        sup = TrainSupervisor(["host0"], d, grace=0.5)
        # the writer's clock is an HOUR behind — wall-based freshness
        # would declare it dead instantly; seq keeps advancing
        for seq in range(3):
            self._write(d, "host0", seq, wall_offset=-3600.0)
            sup.tick()
            time.sleep(0.05)
        assert sup.dead_hosts() == set()

    def test_stalled_seq_dies_despite_fresh_wall_time(self, tmp_path):
        from mmlspark_tpu.resilience.elastic import TrainSupervisor
        d = str(tmp_path)
        sup = TrainSupervisor(["host0"], d, grace=0.15)
        # the writer's clock runs AHEAD: wall-based freshness would keep
        # this ghost alive forever; its seq never advances
        self._write(d, "host0", 7, wall_offset=+3600.0)
        sup.tick()
        assert sup.dead_hosts() == set()       # first sighting: fresh
        time.sleep(0.25)
        self._write(d, "host0", 7, wall_offset=+3600.0)   # same seq
        sup.tick()
        assert sup.dead_hosts() == {"host0"}

    def test_grow_freshness_uses_seq(self, tmp_path):
        from mmlspark_tpu.resilience.elastic import TrainSupervisor
        d = str(tmp_path)
        sup = TrainSupervisor(["host0", "host1"], d, grace=5.0,
                              rejoin_grace=0.0)
        sup._dead.add("host1")
        # joining doc with an ancient wall time but a fresh seq: the
        # grow verdict must land (first sighting = fresh)
        self._write(d, "host1", 3, wall_offset=-3600.0, joining=True)
        sup.tick()
        assert set(sup.joining_hosts()) == {"host1"}

    def test_heartbeat_docs_carry_seq_and_generation(self, tmp_path):
        from mmlspark_tpu.resilience.elastic import HostHeartbeat
        hb = HostHeartbeat("hostX", str(tmp_path), interval=0.02)
        hb.set_generation(4)
        hb.start()
        try:
            time.sleep(0.1)
            doc = json.load(open(hb.path))
            assert doc["seq"] >= 1
            assert doc["generation"] == 4
        finally:
            hb.stop()
        seq1 = doc["seq"]
        doc2 = json.load(open(hb.path))
        assert doc2["seq"] >= seq1            # monotonic

    def test_relaunched_inmesh_host_self_reports_via_joining(self, tmp_path):
        """A mesh member whose heartbeat starts carrying the joining
        flag is a fresh process (killed + relaunched inside the grace
        window): the death pass must drop the OLD membership even though
        the file is beating."""
        from mmlspark_tpu.resilience.elastic import TrainSupervisor
        d = str(tmp_path)
        sup = TrainSupervisor(["host0"], d, grace=60.0)
        self._write(d, "host0", 1)
        sup.tick()
        assert sup.dead_hosts() == set()
        self._write(d, "host0", 2, joining=True)   # relaunch self-report
        sup.tick()
        assert sup.dead_hosts() == {"host0"}


# ----------------------------------------- straggler EVICTION (proactive)

class TestEvictVerdicts:
    """Sustained straggler flags promote to evict verdicts, subject to
    the floors: consecutive-pass count, min_hosts, never the
    coordinator host."""

    def _sup(self, d, hosts=4, evict_after=2, min_hosts=1):
        from mmlspark_tpu.resilience.elastic import TrainSupervisor
        ids = [f"host{i}" for i in range(hosts)]
        sup = TrainSupervisor(ids, d, grace=60.0, min_hosts=min_hosts,
                              evict_after=evict_after,
                              probe=lambda h: 0.0)
        return sup

    def _feed_straggler(self, sup, victim="host2", ratio=5.0):
        for _ in range(16):
            for i in range(len(sup.host_ids)):
                h = f"host{i}"
                sup.anomaly.observe(h, 0.5 if h == victim else 0.1)

    def test_consecutive_flags_promote_to_evict(self, tmp_path):
        sup = self._sup(str(tmp_path), evict_after=3)
        self._feed_straggler(sup)
        sup.tick()
        assert sup.straggler_hosts() == {"host2"}
        assert sup.evict_verdicts() == {}       # 1 < evict_after
        sup.tick()
        assert sup.evict_verdicts() == {}       # 2 < evict_after
        sup.tick()
        assert set(sup.evict_verdicts()) == {"host2"}
        assert sup.dead_hosts() == set()        # a verdict is not a drop

    def test_advisory_only_when_evict_after_zero(self, tmp_path):
        sup = self._sup(str(tmp_path), evict_after=0)
        self._feed_straggler(sup)
        for _ in range(5):
            sup.tick()
        assert sup.straggler_hosts() == {"host2"}
        assert sup.evict_verdicts() == {}

    def test_flag_gap_resets_the_streak(self, tmp_path):
        sup = self._sup(str(tmp_path), evict_after=2)
        self._feed_straggler(sup)
        sup.tick()
        # recovery: refill the victim's window with healthy samples
        for _ in range(64):
            sup.anomaly.observe("host2", 0.1)
        sup.tick()                              # unflagged: streak reset
        assert sup.straggler_hosts() == set()
        self._feed_straggler(sup)
        sup.tick()
        assert sup.evict_verdicts() == {}       # streak restarted at 1

    def test_coordinator_host_is_never_evicted(self, tmp_path):
        sup = self._sup(str(tmp_path), evict_after=1)
        self._feed_straggler(sup, victim="host0")   # lowest alive
        for _ in range(4):
            sup.tick()
        assert sup.straggler_hosts() == {"host0"}   # advisory only
        assert sup.evict_verdicts() == {}

    def test_min_hosts_floor_blocks_evict(self, tmp_path):
        sup = self._sup(str(tmp_path), hosts=2, evict_after=1,
                        min_hosts=2)
        self._feed_straggler(sup, victim="host1")
        for _ in range(4):
            sup.tick()
        assert sup.evict_verdicts() == {}

    def test_mark_evicted_clears_straggler_state(self, tmp_path,
                                                 telemetry_on):
        sup = self._sup(str(tmp_path), evict_after=1)
        self._feed_straggler(sup)
        sup.tick()
        assert set(sup.evict_verdicts()) == {"host2"}
        sup.mark_evicted("host2")
        assert sup.dead_hosts() == {"host2"}
        assert sup.evict_verdicts() == {}
        assert sup.straggler_hosts() == set()
        # detector window forgotten: a rejoin starts clean
        assert "host2" not in sup.anomaly.report()["host_median_s"]
        snap = telemetry.snapshot()
        ev = snap["mmlspark_elastic_evictions_total"]["series"]
        assert [s["labels"]["host"] for s in ev if s["value"] > 0] \
            == ["host2"]

    def test_pending_evict_arms_only_after_checkpoint_boundary(
            self, tmp_path):
        from mmlspark_tpu.resilience.elastic import ElasticFitCoordinator
        coord = ElasticFitCoordinator(
            _elastic_learner(str(tmp_path / "ck")), n_hosts=4,
            grace=60.0, evict_after=1)
        coord._mesh_hosts = {"host0", "host1", "host2", "host3"}
        coord.supervisor._evict["host2"] = time.monotonic()
        assert coord.pending_evict() == set()      # no boundary yet
        coord.note_checkpoint(0, 5)
        assert coord.pending_evict() == {"host2"}

    def test_evict_fault_site(self, tmp_path, telemetry_on):
        from mmlspark_tpu.resilience.elastic import ElasticFitCoordinator
        coord = ElasticFitCoordinator(
            _elastic_learner(str(tmp_path / "ck")), n_hosts=4,
            grace=60.0)
        coord._mesh_hosts = {"host0", "host1", "host2", "host3"}
        faults.configure("elastic.evict:error:1.0", seed=0)
        with pytest.raises(ConnectionError):
            coord._evict({"host2"})


@pytest.mark.chaos
def test_elastic_straggler_evict_and_rejoin(tmp_path, telemetry_on):
    """THE proactive-eviction guarantee, end to end, with SHARDED
    checkpoints: a delayed-but-alive host (heartbeat progress throttled
    5x while a ``delay`` fault at ``elastic.step`` paces the fleet) is
    flagged by the rolling-MAD detector, promoted to an evict verdict
    after 2 consecutive passes, and dropped at a committed checkpoint
    boundary — the 4-shard checkpoint written on the 4-host mesh resumes
    on the 3-host mesh (write on N, resume on N-1), bit-exact against
    the committed shards (replays only, no lost steps). Once its cadence
    recovers the evicted host rejoins through the ordinary grow path and
    the fit finishes on the full fleet."""
    from flax import serialization
    from mmlspark_tpu.models.trainer import TpuLearner, _params_digest
    from mmlspark_tpu.resilience import ckpt as ckptlib
    from mmlspark_tpu.resilience.elastic import ElasticFitCoordinator

    ck = str(tmp_path / "ck")
    rng = np.random.default_rng(1)
    n = 256
    x = rng.normal(size=(n, 4)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int64)
    df = DataFrame({"features": object_column([r for r in x]),
                    "label": y})
    learner = (TpuLearner()
               .setModelConfig({"type": "mlp", "hidden": [4],
                                "num_classes": 2})
               .setEpochs(3).setBatchSize(8).setLearningRate(0.05)
               .setDeviceDataCap(1)
               .setCheckpointDir(ck).setCheckpointEverySteps(4)
               .setCheckpointShards(4))
    faults.configure("elastic.step:delay:1.0:0.04", seed=11)
    coord = ElasticFitCoordinator(learner, n_hosts=4, grace=0.4,
                                  heartbeat_interval=0.05,
                                  rejoin_grace=0.1, evict_after=2)
    coord.heartbeats["host3"].throttle(5)

    ckpt_snaps = {}
    done = threading.Event()

    def chaos_script():
        # snapshot every committed shard set (pruning races the
        # assertions below), and relaunch the victim HEALTHY once the
        # evict re-mesh is underway
        relaunched = False
        while not done.is_set():
            for f in (os.listdir(ck) if os.path.isdir(ck) else []):
                if f.endswith(".msgpack") and f not in ckpt_snaps:
                    try:
                        ckpt_snaps[f] = open(os.path.join(ck, f),
                                             "rb").read()
                    except OSError:
                        continue
            if not relaunched and "host3" in coord.supervisor.dead_hosts():
                coord.relaunch_host("host3")   # cadence recovered
                relaunched = True
            time.sleep(0.005)

    t = threading.Thread(target=chaos_script, daemon=True)
    t.start()
    try:
        model = coord.fit(df)
    finally:
        done.set()
        t.join(timeout=5)
    assert np.isfinite(model._final_loss)

    # the straggler was EVICTED (proactively — it never died) and then
    # readmitted through the grow path
    snap = telemetry.snapshot()
    ev = snap["mmlspark_elastic_evictions_total"]["series"]
    assert [s["labels"]["host"] for s in ev if s["value"] > 0] \
        == ["host3"]
    assert snap["mmlspark_elastic_grows_total"]["series"][0]["value"] >= 1
    assert coord.supervisor.dead_hosts() == set()
    assert coord.attempts[-1]["hosts"] == ["host0", "host1", "host2",
                                           "host3"]
    evict_rec = next(a for a in coord.attempts if "evict_recovery_s" in a)
    assert evict_rec["evict_recovery_s"] > 0

    # replays-only: every step of every epoch committed at least once
    assert {(e, s) for (e, s) in coord.committed} \
        >= {(e, s) for e in range(3) for s in range(32)}

    # bit-exact sharded resume: the post-evict attempt restored params
    # whose digest equals the digest of the committed shard set it
    # resumed from (4 shards written on the 4-host mesh, reassembled on
    # the 3-host mesh)
    final = evict_rec
    assert final["resume_pos"] is not None
    epoch, step = final["resume_pos"]
    name = (f"ckpt_{epoch:05d}.msgpack" if step is None
            else f"ckpt_{epoch:05d}_s{step:07d}.msgpack")
    assert ckptlib.parse_head(ckpt_snaps[name]) is not None
    flat = {}
    for sname in ckptlib.parse_head(ckpt_snaps[name]):
        flat.update(serialization.msgpack_restore(ckpt_snaps[sname]))
    state = ckptlib.unflatten_state(flat)
    assert _params_digest(state["params"]) == final["resume_digest"]


# ------------------------------------------------ sharded checkpoint unit

class TestShardedCheckpoints:
    def _state(self):
        rng = np.random.default_rng(0)
        return {"params": {"dense": {"kernel": rng.normal(
                    size=(16, 8)).astype(np.float32),
                    "bias": rng.normal(size=(8,)).astype(np.float32)}},
                "opt": {"0": {"mu": rng.normal(size=(16, 8)).astype(
                    np.float32)}, "1": {}}}

    def test_flatten_round_trip_keeps_empty_dicts(self):
        from mmlspark_tpu.resilience import ckpt
        flat = ckpt.flatten_state(self._state())
        back = ckpt.unflatten_state(flat)
        assert back["opt"]["1"] == {}
        np.testing.assert_array_equal(
            back["params"]["dense"]["kernel"],
            self._state()["params"]["dense"]["kernel"])

    def test_partition_is_deterministic_and_covers(self):
        from mmlspark_tpu.resilience import ckpt
        sizes = [100, 1, 1, 100, 50, 50, 1]
        parts = ckpt.partition_leaves(sizes, 3)
        assert parts == ckpt.partition_leaves(sizes, 3)
        assert sorted(i for p in parts for i in p) == list(range(7))
        assert len(parts) == 3

    def test_publish_sharded_commit_and_verify(self, tmp_path):
        from mmlspark_tpu.resilience import ckpt
        d = str(tmp_path)
        path = os.path.join(d, "ckpt_00001_s0000003.msgpack")
        ckpt.publish_sharded(path, [b"shard-a" * 10, b"shard-b" * 20])
        # head under the canonical name + 2 shard files + manifest
        assert ckpt.parse_head(open(path, "rb").read()) == \
            ["ckpt_00001_s0000003.shard_0.msgpack",
             "ckpt_00001_s0000003.shard_1.msgpack"]
        assert ckpt.verify(d, "ckpt_00001_s0000003.msgpack")
        files = ckpt.load_manifest(d)
        entry = files["ckpt_00001_s0000003.msgpack"]
        assert len(entry["shards"]) == 2
        blobs = ckpt.read_shards(
            d, ckpt.parse_head(open(path, "rb").read()))
        assert blobs == [b"shard-a" * 10, b"shard-b" * 20]

    def test_torn_shard_disqualifies_whole_candidate(self, tmp_path,
                                                     telemetry_on):
        from mmlspark_tpu.resilience import ckpt
        d = str(tmp_path)
        ckpt.publish_sharded(os.path.join(d, "ckpt_00001.msgpack"),
                             [b"old-a", b"old-b"])
        ckpt.publish_sharded(os.path.join(d, "ckpt_00002.msgpack"),
                             [b"new-a", b"new-b"])
        # tear the newest candidate's second shard (truncation)
        with open(os.path.join(d, "ckpt_00002.shard_1.msgpack"),
                  "wb") as f:
            f.write(b"n")
        assert not ckpt.verify(d, "ckpt_00002.msgpack")
        assert ckpt.verify(d, "ckpt_00001.msgpack")   # fallback intact
        snap = telemetry.snapshot()
        assert snap["mmlspark_ckpt_corrupt_total"]["series"][0]["value"] \
            >= 1
        assert snap["mmlspark_ckpt_shards_written_total"]["series"][0][
            "value"] == 4

    def test_missing_shard_disqualifies(self, tmp_path, telemetry_on):
        from mmlspark_tpu.resilience import ckpt
        d = str(tmp_path)
        ckpt.publish_sharded(os.path.join(d, "ckpt_00001.msgpack"),
                             [b"a", b"b", b"c"])
        os.remove(os.path.join(d, "ckpt_00001.shard_2.msgpack"))
        assert not ckpt.verify(d, "ckpt_00001.msgpack")

    def test_shard_content_hash_checked_at_read(self, tmp_path):
        from mmlspark_tpu.resilience import ckpt
        d = str(tmp_path)
        path = os.path.join(d, "ckpt_00001.msgpack")
        ckpt.publish_sharded(path, [b"aaaa", b"bbbb"])
        # same-size corruption: size verify passes, sha256 must not
        with open(os.path.join(d, "ckpt_00001.shard_0.msgpack"),
                  "wb") as f:
            f.write(b"zzzz")
        assert ckpt.verify(d, "ckpt_00001.msgpack")   # sizes still match
        with pytest.raises(ckpt.CorruptCheckpoint):
            ckpt.read_shards(d, ["ckpt_00001.shard_0.msgpack",
                                 "ckpt_00001.shard_1.msgpack"])

    def test_prune_takes_shards_with_the_head(self, tmp_path):
        from mmlspark_tpu.resilience import ckpt
        d = str(tmp_path)
        ckpt.publish_sharded(os.path.join(d, "ckpt_00001.msgpack"),
                             [b"a", b"b"])
        ckpt.prune(d, ["ckpt_00001.msgpack"])
        assert [f for f in os.listdir(d) if f.endswith(".msgpack")] == []

    def test_shard_fault_site(self, tmp_path):
        from mmlspark_tpu.resilience import ckpt
        faults.configure("ckpt.shard:error:1.0", seed=0)
        with pytest.raises(ConnectionError):
            ckpt.write_shard(str(tmp_path / "ckpt_00001.shard_0.msgpack"),
                             b"x")

    def test_trainer_sharded_kill_and_resume(self, tmp_path):
        """A plain (non-elastic) fit with checkpointShards: the 3-shard
        checkpoint restores bit-exact into a resumed fit."""
        from mmlspark_tpu.models.trainer import TpuLearner, _params_digest

        def learner():
            return (TpuLearner()
                    .setModelConfig({"type": "mlp", "hidden": [4],
                                     "num_classes": 2})
                    .setEpochs(2).setBatchSize(8).setLearningRate(0.05)
                    .setShuffle(False).setDeviceDataCap(1)
                    .setCheckpointDir(str(tmp_path / "ck"))
                    .setCheckpointShards(3))
        df = _toy_df(64)
        baseline = learner().setCheckpointDir(
            str(tmp_path / "ck_base")).fit(df)
        # interrupted run: epoch 0 only, then a fresh learner resumes
        first = learner().setEpochs(1).fit(df)
        assert os.path.exists(
            str(tmp_path / "ck" / "ckpt_00000.shard_0.msgpack"))
        resumed = learner().fit(df)
        assert _params_digest(resumed.getModelParams()) == \
            _params_digest(baseline.getModelParams())


# --------------------------------------------- fleet health on /healthz

def test_fleet_health_surfaces_on_healthz(tmp_path):
    """An operator watching /healthz sees the elastic fleet: hosts
    alive, stragglers, pending verdicts, rendezvous generation."""
    from mmlspark_tpu.io.http.server import HTTPSource
    from mmlspark_tpu.resilience.elastic import (ElasticFitCoordinator,
                                                 _register_fleet,
                                                 _unregister_fleet,
                                                 fleet_health)
    assert fleet_health() is None
    coord = ElasticFitCoordinator(_elastic_learner(str(tmp_path / "ck")),
                                  n_hosts=4, grace=60.0, evict_after=2)
    coord._mesh_hosts = {"host0", "host1", "host2", "host3"}
    coord.supervisor._dead.add("host3")
    coord.supervisor._flagged.add("host2")
    coord.supervisor._evict["host2"] = 0.0
    coord.supervisor._joining["host3"] = 0.0
    _register_fleet(coord)
    try:
        h = fleet_health()
        assert h["hosts_alive"] == 3
        assert h["dead"] == ["host3"]
        assert h["stragglers"] == ["host2"]
        assert h["pending_evict"] == ["host2"]
        assert h["pending_grow"] == ["host3"]
        assert h["rendezvous_generation"] == 0
        src = HTTPSource(name="t", host="127.0.0.1", port=0)
        try:
            body = json.loads(urllib.request.urlopen(
                src.url + "healthz", timeout=5).read())
            assert body["elastic"]["hosts_alive"] == 3
            assert body["elastic"]["pending_evict"] == ["host2"]
        finally:
            src.close()
    finally:
        _unregister_fleet(coord)
    assert fleet_health() is None


# ------------------------------------- rendezvous protocol (generation)

class TestRendezvousProtocol:
    """Doc election, generation monotonicity, stale-generation refusal,
    and the deterministic unwind point — all unit-level (the real
    2-process teardown/re-init lives in test_elastic_multiproc.py's
    slow tier)."""

    def _rdzv(self, d, host="host0"):
        from mmlspark_tpu.parallel.distributed import RendezvousCoordinator
        return RendezvousCoordinator(str(d), host)

    def test_propose_and_read(self, tmp_path):
        r = self._rdzv(tmp_path)
        doc = r.propose(["host0", "host1"])
        assert doc["generation"] == 1
        assert doc["ranks"] == {"host0": 0, "host1": 1}
        assert r.read()["generation"] == 1
        doc2 = r.propose(["host0"])
        assert doc2["generation"] == 2        # monotonic past the doc

    def test_only_the_leader_may_propose(self, tmp_path):
        from mmlspark_tpu.parallel.distributed import RendezvousError
        r = self._rdzv(tmp_path, host="host1")
        with pytest.raises(RendezvousError, match="leader"):
            r.propose(["host0", "host1"])

    def test_await_membership_parks_until_named(self, tmp_path):
        from mmlspark_tpu.parallel.distributed import RendezvousError
        r = self._rdzv(tmp_path, host="host2")
        leader = self._rdzv(tmp_path, host="host0")
        leader.propose(["host0", "host1"])    # gen 1: host2 NOT named
        with pytest.raises(RendezvousError, match="named"):
            r.await_membership(1, timeout=0.3)
        leader.propose(["host0", "host1", "host2"])
        doc = r.await_membership(2, timeout=1.0)
        assert doc["ranks"]["host2"] == 2

    def test_stale_generation_can_never_be_joined(self, tmp_path):
        from mmlspark_tpu.parallel.distributed import RendezvousError
        r = self._rdzv(tmp_path)
        doc = r.propose(["host0", "host1"])
        r.generation = 5                      # we already held gen 5
        with pytest.raises(RendezvousError, match="[Ss]tale"):
            r.join(doc)                       # gen 1 < 5: refused

    def test_join_refuses_a_doc_that_omits_us(self, tmp_path):
        from mmlspark_tpu.parallel.distributed import RendezvousError
        r = self._rdzv(tmp_path, host="host9")
        leader = self._rdzv(tmp_path, host="host0")
        doc = leader.propose(["host0", "host1"])
        with pytest.raises(RendezvousError, match="include"):
            r.join(doc)

    def test_rendezvous_fault_site(self, tmp_path):
        faults.configure("distributed.rendezvous:error:1.0", seed=0)
        r = self._rdzv(tmp_path)
        with pytest.raises(ConnectionError):
            r.propose(["host0"])

    def test_deterministic_unwind_at_boundary(self, tmp_path):
        """check_rendezvous raises RendezvousPending exactly when the
        committed step reaches the doc's unwind_at — the same step on
        every process."""
        from mmlspark_tpu.resilience.elastic import (ElasticFitCoordinator,
                                                     RendezvousPending)
        coord = ElasticFitCoordinator(
            _elastic_learner(str(tmp_path / "ck")), n_hosts=2,
            grace=60.0)
        rdzv = self._rdzv(tmp_path / "ck" / "heartbeats", host="host1")
        leader = self._rdzv(tmp_path / "ck" / "heartbeats", host="host0")
        os.makedirs(str(tmp_path / "ck" / "heartbeats"), exist_ok=True)
        coord._rdzv = rdzv
        coord._multiproc = True
        coord._mesh_hosts = {"host0", "host1"}
        coord.check_rendezvous(0, 3)          # no doc: no-op
        leader.propose(["host0", "host1"], unwind_at=(0, 6))
        coord.check_rendezvous(0, 4)          # before the boundary
        coord.check_rendezvous(0, 5)
        time.sleep(0.06)                      # past the stat throttle
        with pytest.raises(RendezvousPending):
            coord.check_rendezvous(0, 6)

    @pytest.mark.chaos
    def test_rendezvous_failure_falls_back_to_full_relaunch(
            self, tmp_path, telemetry_on):
        """Injected faults at distributed.rendezvous: the cycle retries
        with backoff and then falls back to relaunch-at-full-size
        (ElasticFleetLost) instead of hanging the fleet."""
        from mmlspark_tpu.resilience.elastic import (ElasticFitCoordinator,
                                                     ElasticFleetLost)
        coord = ElasticFitCoordinator(
            _elastic_learner(str(tmp_path / "ck")), n_hosts=2,
            grace=60.0, max_failures=2)
        rdzv = self._rdzv(tmp_path / "ck" / "heartbeats", host="host0")
        os.makedirs(str(tmp_path / "ck" / "heartbeats"), exist_ok=True)
        coord._rdzv = rdzv
        coord._multiproc = True
        coord._mesh_hosts = {"host0", "host1"}
        hb = coord.heartbeats["host0"]
        faults.configure("distributed.rendezvous:error:1.0", seed=0)
        t0 = time.monotonic()
        with pytest.raises(ElasticFleetLost, match="relaunch"):
            coord._rendezvous_cycle(hb)
        # retried with backoff (2 attempts -> at least one 0.2s sleep)
        assert time.monotonic() - t0 >= 0.2
        assert faults.snapshot()["distributed.rendezvous"][0][
            "injected"] >= 2


# -------------------------------------------------- chaos site coverage
#
# graftlint's `chaos-test-coverage` rule requires every faults.SITES
# entry to be exercised by at least one test; these one-shot tests arm
# each previously-unrehearsed site at rate 1.0 and drive the REAL code
# path through it (the injected fault must surface exactly where the
# recovery design says it does).

@pytest.mark.chaos
class TestChaosSiteCoverage:
    def test_powerbi_post_site(self):
        from mmlspark_tpu.io import powerbi
        faults.configure("powerbi.post:error:1.0")
        with pytest.raises(faults.InjectedFault):
            powerbi._post_batch("http://127.0.0.1:9/x", "[]", timeout=0.2)

    def test_dataplane_put_site(self):
        from mmlspark_tpu.parallel import mesh as meshlib
        faults.configure("dataplane.put:error:1.0")
        m = meshlib.make_mesh({"data": 1})
        with pytest.raises(faults.InjectedFault):
            meshlib.put_global_batch(np.zeros((2, 2), np.float32), m)

    def test_dataplane_allgather_site(self):
        from mmlspark_tpu.parallel import dataplane
        faults.configure("dataplane.allgather:error:1.0")
        with pytest.raises(faults.InjectedFault):
            dataplane.allgather_bytes(b"payload")

    def test_supervisor_probe_site(self):
        from types import SimpleNamespace
        faults.configure("supervisor.probe:error:1.0")
        sup = FleetSupervisor(SimpleNamespace(workers=[]))
        w = SimpleNamespace(host="127.0.0.1", control=9, proc=None)
        # the injected probe fault reads as "unhealthy", never raises
        assert sup._healthy(w) is False
        assert faults.snapshot()["supervisor.probe"][0]["injected"] == 1

    def test_http_request_site(self):
        from mmlspark_tpu.io.http.transformer import HTTPTransformer
        faults.configure("http.request:error:1.0")
        df = DataFrame({"req": object_column(
            [{"url": "http://127.0.0.1:9/", "method": "GET"}])})
        t = (HTTPTransformer().setInputCol("req").setOutputCol("resp")
             .setRetries(0).setTrace(False))
        out = t.transform(df).col("resp")
        assert out[0].get("error")          # fault surfaced per-row

    def test_http_debug_site_answers_503(self):
        w = WorkerServer("127.0.0.1")
        try:
            faults.configure("http.debug:error:1.0:0:1")  # first GET only
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get_json(f"http://127.0.0.1:{w.control_port}/healthz")
            assert ei.value.code == 503
            # budget spent: the debug plane recovers on the next probe
            code, h = _get_json(
                f"http://127.0.0.1:{w.control_port}/healthz")
            assert code == 200 and h["ok"] is True
        finally:
            w.close()

    def test_elastic_remesh_site(self, tmp_path):
        from mmlspark_tpu.resilience.elastic import ElasticFitCoordinator
        faults.configure("elastic.remesh:error:1.0")
        coord = ElasticFitCoordinator(n_hosts=2,
                                      checkpoint_dir=str(tmp_path))
        with pytest.raises(faults.InjectedFault):
            coord._remesh(["host1"])

    def test_downloader_fetch_site(self):
        from mmlspark_tpu.models.downloader import RemoteRepo
        faults.configure("downloader.fetch:error:1.0")
        with pytest.raises(faults.InjectedFault):
            RemoteRepo("http://127.0.0.1:9").listSchemas()

    def test_codegen_write_site(self, tmp_path):
        from mmlspark_tpu import codegen
        faults.configure("codegen.write:error:1.0")
        with pytest.raises(faults.InjectedFault):
            codegen.generate_r_wrappers(str(tmp_path / "wrappers.R"))
