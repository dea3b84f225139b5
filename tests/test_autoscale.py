"""SLO-driven elastic serving fleet: leader-lease rendezvous proposals,
autoscaler hysteresis (grow/shrink windows + cooldown), the reconciler's
desired-vs-observed convergence with graceful drain, burn-severity
Retry-After, the four new chaos sites (`autoscale.verdict`,
`fleet.spawn`, `fleet.drain`, `distributed.lease`), and the combined
chaos e2e: bursty load -> breach -> grow warm from bundle -> kill ->
reconcile same lineage -> idle -> shrink with zero-loss drain ->
/healthz ok."""

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
from mmlspark_tpu.io.http.fleet import (ProcessHTTPSource,
                                        ReplayServingLoop, _Worker,
                                        fleet_doc)
from mmlspark_tpu.io.http.server import HTTPSource
from mmlspark_tpu.io.http.worker import WorkerServer
from mmlspark_tpu.core.utils import object_column
from mmlspark_tpu.models.modules import build_model
from mmlspark_tpu.resilience import faults
from mmlspark_tpu.resilience.autoscale import ServingAutoscaler
from mmlspark_tpu.resilience.policy import RetryPolicy
from mmlspark_tpu.resilience.reconciler import FleetReconciler
from mmlspark_tpu.telemetry.slo import SLOEngine
from mmlspark_tpu.telemetry.timeseries import TimeSeriesSampler


@pytest.fixture
def tel():
    telemetry.enable()
    telemetry.registry.reset()
    yield telemetry
    telemetry.disable()


@pytest.fixture(autouse=True)
def _clear_faults():
    yield
    faults.clear()


def _counter_total(name):
    snap = telemetry.snapshot()
    return sum(s["value"] for s in snap.get(name, {}).get("series", []))


def _post(url, data: bytes, timeout=10.0):
    req = urllib.request.Request(url, data=data)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read().decode()


def _get_json(url, timeout=5.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, json.loads(r.read())


# ------------------------------------------------- leader lease (protocol)

class TestLeaderLease:
    def _lease(self, d, host="host0", timeout=0.2):
        from mmlspark_tpu.parallel.distributed import LeaderLease
        return LeaderLease(str(d), host, timeout=timeout)

    def test_acquire_renew_held(self, tmp_path):
        lease = self._lease(tmp_path)
        assert not lease.held() and lease.expired()
        lease.acquire()
        assert lease.held() and lease.term == 1
        seq0 = lease.read()["seq"]
        lease.renew()
        assert lease.read()["seq"] == seq0 + 1
        assert not lease.expired()

    def test_takeover_refused_while_fresh(self, tmp_path):
        from mmlspark_tpu.parallel.distributed import RendezvousError
        a = self._lease(tmp_path, "host0")
        b = self._lease(tmp_path, "host1")
        a.acquire()
        b.observe()                       # b starts watching a fresh lease
        with pytest.raises(RendezvousError, match="held fresh"):
            b.acquire()

    def test_expired_lease_taken_over_and_stale_renew_refused(
            self, tmp_path):
        from mmlspark_tpu.parallel.distributed import RendezvousError
        a = self._lease(tmp_path, "host0", timeout=0.15)
        b = self._lease(tmp_path, "host1", timeout=0.15)
        a.acquire()
        b.observe()
        time.sleep(0.2)                   # a goes silent past the window
        assert b.expired()
        b.acquire()                       # takeover bumps the term
        assert b.term == 2 and b.read()["holder"] == "host1"
        with pytest.raises(RendezvousError, match="lost the leader"):
            a.renew()                     # the deposed leader can't renew

    def test_freshness_is_reader_clock_seq_advancement(self, tmp_path):
        """A lease doc with a wildly future wall time is still expired
        once its (term, seq) stops advancing — only reader-observed
        advancement counts (the PR 10 heartbeat posture)."""
        lease = self._lease(tmp_path, "host1", timeout=0.15)
        doc = {"holder": "host0", "term": 3, "seq": 7,
               "time": time.time() + 1e6}
        (tmp_path / "lease.json").write_text(json.dumps(doc))
        assert not lease.expired()        # first watch: wait the window out
        time.sleep(0.2)
        assert lease.expired()


class TestLeaseRendezvous:
    def _rdzv(self, d, host="host0", lease_timeout=0.2):
        from mmlspark_tpu.parallel.distributed import RendezvousCoordinator
        return RendezvousCoordinator(str(d), host,
                                     lease_timeout=lease_timeout)

    def test_propose_acquires_and_stamps_lease_term(self, tmp_path):
        r = self._rdzv(tmp_path)
        doc = r.propose(["host0", "host1"])
        assert doc["lease_term"] == 1 and r.lease.held()
        doc2 = r.propose(["host0", "host1"])
        assert doc2["generation"] == 2 and doc2["lease_term"] == 1

    def test_fresh_holder_proposes_even_when_not_lowest_rank(
            self, tmp_path):
        r1 = self._rdzv(tmp_path, "host1")
        r1.lease.acquire()
        doc = r1.propose(["host0", "host1"])   # holder beats rank order
        assert doc["ranks"]["host0"] == 0      # ranks still sorted
        assert doc["lease_term"] == 1

    def test_nonholder_refused_while_lease_fresh(self, tmp_path):
        from mmlspark_tpu.parallel.distributed import RendezvousError
        r1 = self._rdzv(tmp_path, "host1")
        r0 = self._rdzv(tmp_path, "host0")
        r1.lease.acquire()
        r0.lease.observe()
        with pytest.raises(RendezvousError, match="fresh leader lease"):
            r0.propose(["host0", "host1"])

    def test_expired_lease_taken_by_lowest_rank_fresh_host(self, tmp_path):
        r1 = self._rdzv(tmp_path, "host1")
        r0 = self._rdzv(tmp_path, "host0")
        r1.lease.acquire()
        r0.lease.observe()
        time.sleep(0.25)                  # holder silent past the window
        doc = r0.propose(["host0", "host2"])
        assert r0.lease.term == 2         # takeover bumped the term
        assert doc["lease_term"] == 2

    def test_stale_leaders_late_proposal_refused(self, tmp_path):
        """The doc-race fix: a deposed leader can neither renew nor let
        its late write stand — followers refuse docs stamped with an
        outdated lease term, and the stale propose() raises."""
        from mmlspark_tpu.parallel.distributed import RendezvousError
        old = self._rdzv(tmp_path, "host0")
        new = self._rdzv(tmp_path, "host1")
        follower = self._rdzv(tmp_path, "host2")
        old.propose(["host0", "host1", "host2"])      # term 1, gen 1
        new.lease.observe()
        time.sleep(0.25)
        new.lease.acquire()                           # term 2: old deposed
        with pytest.raises(RendezvousError, match="lease"):
            old.propose(["host0", "host1", "host2"])  # refused, not raced
        # a forged stale-term doc is refused by generation at followers
        doc = json.loads((tmp_path / "rendezvous.json").read_text())
        doc["generation"] = 99
        doc["lease_term"] = 1             # stamped with the deposed term
        (tmp_path / "rendezvous.json").write_text(json.dumps(doc))
        with pytest.raises(RendezvousError, match="no rendezvous"):
            follower.await_membership(99, timeout=0.3)

    def test_elect_leader_prefers_fresh_holder(self, tmp_path):
        r1 = self._rdzv(tmp_path, "host1")
        r0 = self._rdzv(tmp_path, "host0")
        assert r0.elect_leader(["host0", "host1"], max_age=0.0) == "host0"
        r1.lease.acquire()
        assert r0.elect_leader(["host0", "host1"], max_age=0.0) == "host1"
        assert r1.elect_leader(["host0", "host1"], max_age=0.0) == "host1"
        # holder not a member (evicted): falls back to rank order
        assert r0.elect_leader(["host0", "host2"], max_age=0.0) == "host0"

    @pytest.mark.chaos
    def test_chaos_lease_site(self, tmp_path, tel):
        """One-shot chaos at `distributed.lease`: the first lease
        round-trip faults (counted), the retried acquire succeeds."""
        faults.configure("distributed.lease:error:1.0:0:1", seed=0)
        r = self._rdzv(tmp_path)
        with pytest.raises(ConnectionError):
            r.lease.acquire()
        r.lease.acquire()                 # budget spent: clean retry
        assert r.lease.held()
        assert _counter_total("mmlspark_faults_injected_total") == 1


# ------------------------------------------------ in-process fleet helpers

class _Echo:
    def transform(self, df):
        return df.withColumn("reply", object_column(
            [json.dumps({"echo": v}) for v in df.col("value")]))


def _inproc_spawner(servers, **worker_kwargs):
    """A reconciler/supervisor spawn callable over IN-PROCESS
    WorkerServers (subprocess spawn cost is not what these tests
    measure). Respawns reuse the old incarnation's ports — the same
    lineage the subprocess respawn machinery preserves. The old
    incarnation's in-process server is closed first (a subprocess dies
    with its sockets; an in-process one must release them to rebind)."""
    def spawn(wi, old):
        if old is not None:
            for ws in servers:
                if ws.control_port == old.control:
                    try:
                        ws.close()
                    except Exception:
                        pass
        ws = WorkerServer("127.0.0.1",
                          port=old.port if old is not None else 0,
                          control_port=old.control if old is not None
                          else 0, **worker_kwargs)
        servers.append(ws)
        return _Worker("127.0.0.1", ws.source.port, ws.control_port,
                       spawn=False)
    return spawn


def _slo_latency(sampler, fast=5.0, slow=10.0, threshold=0.05,
                 hist="mmlspark_http_request_seconds"):
    return SLOEngine([{"name": "p99-latency", "kind": "latency",
                       "hist": hist, "threshold_s": threshold,
                       "target": 0.99, "windows": (fast, slow),
                       "shed_on_breach": True}], sampler=sampler)


def _mk_scaler(tmp=None, n=1, min_workers=1, max_workers=3,
               windows=(5.0, 10.0), **kw):
    """(servers, source, reconciler, autoscaler, sampler, hist): a full
    in-process control plane over a synthetic latency histogram driven
    by the tests' own clock."""
    hist = telemetry.registry.histogram(
        "test_autoscale_latency_seconds", "synthetic request latency")
    sampler = TimeSeriesSampler(interval=1.0)
    slo = _slo_latency(sampler, fast=windows[0], slow=windows[1],
                       hist="test_autoscale_latency_seconds")
    servers = []
    spawn = _inproc_spawner(servers)
    handles = [spawn(i, None) for i in range(n)]
    source = ProcessHTTPSource(workers=handles)
    rec = FleetReconciler(source, n, spawn=spawn,
                          min_workers=min_workers,
                          max_workers=max_workers)
    asc = ServingAutoscaler(slo, rec, **kw)
    return servers, source, rec, asc, sampler, hist


def _close_all(servers, source):
    for ws in servers:
        try:
            ws.close()
        except Exception:
            pass
    source.close()


# ------------------------------------------------- autoscaler (hysteresis)

class TestAutoscalerHysteresis:
    T0 = 1000.0

    def _burn(self, hist, n=20, v=0.2):
        for _ in range(n):
            hist.observe(v)

    def test_sustained_breach_grows_once_then_cooldown(self, tel):
        servers, src, rec, asc, sampler, hist = _mk_scaler(
            grow_window=2.0, shrink_window=5.0, cooldown=30.0)
        try:
            verdicts = []
            for i in range(10):
                t = self.T0 + i
                self._burn(hist)
                sampler.tick(now=t)
                v = asc.tick(now=t)
                if v:
                    verdicts.append((i, v))
            # one grow at the window edge (the first sampler tick seeds
            # baselines, so the breach clock starts at tick 1), then the
            # cooldown absorbs the still-burning objective
            assert verdicts == [(3, "grow")]
            assert rec.desired == 2
            rec.tick()
            assert rec.observed() == 2 and rec.converged()
            assert asc.state()["last_verdict"] == "grow"
        finally:
            _close_all(servers, src)

    def test_breach_shorter_than_grow_window_produces_no_verdict(
            self, tel):
        """Hysteresis, entry side: a breach that clears before the grow
        window elapses leaves no verdict behind."""
        servers, src, rec, asc, sampler, hist = _mk_scaler(
            windows=(2.0, 4.0), grow_window=6.0, shrink_window=60.0,
            cooldown=5.0)
        try:
            count0 = _counter_total("mmlspark_autoscale_verdicts")
            for i in range(20):
                t = self.T0 + i
                if i == 1:
                    self._burn(hist)   # one burst: breach clears in ~2 s
                sampler.tick(now=t)
                assert asc.tick(now=t) is None
            assert rec.desired == 1
            assert _counter_total(
                "mmlspark_autoscale_verdicts") == count0
        finally:
            _close_all(servers, src)

    def test_burn_recovering_inside_cooldown_produces_zero_verdicts(
            self, tel):
        """The satellite guarantee: a burn that recovers INSIDE the
        post-verdict cooldown produces zero further verdicts — no
        second grow when the cooldown ends, and no rebound shrink."""
        servers, src, rec, asc, sampler, hist = _mk_scaler(
            windows=(2.0, 4.0), grow_window=1.0, shrink_window=60.0,
            cooldown=15.0)
        try:
            verdicts = []
            for i in range(40):
                t = self.T0 + i
                if i <= 4:
                    self._burn(hist)   # burn stops right after the grow
                sampler.tick(now=t)
                v = asc.tick(now=t)
                if v:
                    verdicts.append((i, v))
            # exactly one grow; the burn recovered (windows drained) at
            # ~i=9, well inside the 15 s cooldown — nothing else fires
            assert verdicts == [(verdicts[0][0], "grow")]
            assert verdicts[0][0] <= 5
            assert rec.desired == 2
            assert _counter_total(
                "mmlspark_autoscale_verdicts") == 1
        finally:
            _close_all(servers, src)

    def test_square_wave_bounded_to_one_transition_per_cooldown(self, tel):
        """Grow->shrink->grow oscillation under a square-wave load is
        bounded: at most one verdict per cooldown window."""
        cooldown = 10.0
        servers, src, rec, asc, sampler, hist = _mk_scaler(
            grow_window=1.0, shrink_window=1.0, cooldown=cooldown,
            max_workers=4)
        try:
            duration = 60
            verdicts = []
            for i in range(duration):
                t = self.T0 + i
                if (i // 5) % 2 == 0:       # 5 s on / 5 s off square wave
                    self._burn(hist)
                sampler.tick(now=t)
                v = asc.tick(now=t)
                if v:
                    verdicts.append((t, v))
            assert verdicts, "square wave produced no verdicts at all"
            for (t1, _), (t2, _) in zip(verdicts, verdicts[1:]):
                assert t2 - t1 >= cooldown
            assert len(verdicts) <= duration / cooldown + 1
        finally:
            _close_all(servers, src)

    def test_idle_shrinks_to_floor_with_graceful_drain(self, tel):
        servers, src, rec, asc, sampler, hist = _mk_scaler(
            n=3, min_workers=1, max_workers=3, grow_window=1.0,
            shrink_window=3.0, cooldown=4.0, idle_rows_per_worker=1.0)
        try:
            desired_seen = []
            for i in range(30):
                t = self.T0 + i
                sampler.tick(now=t)
                asc.tick(now=t)
                desired_seen.append(rec.desired)
            assert rec.desired == 1           # floored at min_workers
            deadline = time.monotonic() + 10
            while not rec.converged() and time.monotonic() < deadline:
                rec.tick()
                time.sleep(0.05)
            assert rec.observed() == 1 and rec.converged()
            retired = [wi for wi, w in enumerate(src.workers) if w.retired]
            assert len(retired) == 2          # drained, not killed hot
            assert _counter_total(
                "mmlspark_fleet_workers_retired") >= 2
        finally:
            _close_all(servers, src)

    def test_grow_capped_at_max_workers(self, tel):
        servers, src, rec, asc, sampler, hist = _mk_scaler(
            max_workers=2, grow_window=1.0, cooldown=2.0)
        try:
            for i in range(20):
                t = self.T0 + i
                self._burn(hist)
                sampler.tick(now=t)
                asc.tick(now=t)
            assert rec.desired == 2           # capped, no runaway
        finally:
            _close_all(servers, src)

    @pytest.mark.chaos
    def test_chaos_verdict_site_skips_once_then_fires(self, tel):
        """One-shot chaos at `autoscale.verdict`: the injected fault
        skips that tick's verdict (counted) without killing anything;
        the pressure persists and the next tick applies it."""
        faults.configure("autoscale.verdict:error:1.0:0:1", seed=0)
        servers, src, rec, asc, sampler, hist = _mk_scaler(
            grow_window=1.0, cooldown=2.0)
        try:
            applied = []
            for i in range(4):
                t = self.T0 + i
                self._burn(hist)
                sampler.tick(now=t)
                v = asc.tick(now=t)
                if v:
                    applied.append(i)
            # breach clocks in at tick 1 (tick 0 seeds the sampler), the
            # tick-2 verdict is skipped by the fault, tick 3 applies it
            assert applied == [3]
            assert rec.desired == 2
            assert _counter_total(
                "mmlspark_autoscale_verdicts_skipped") == 1
            assert _counter_total("mmlspark_faults_injected_total") == 1
        finally:
            _close_all(servers, src)


# ------------------------------------------------------ reconciler (loop)

class TestReconciler:
    def test_converges_up_and_down(self, tel):
        servers = []
        spawn = _inproc_spawner(servers)
        src = ProcessHTTPSource(workers=[spawn(0, None)])
        rec = FleetReconciler(src, 1, spawn=spawn, max_workers=4)
        try:
            rec.set_desired(3)
            rec.tick()
            assert rec.observed() == 3
            rec.set_desired(1)
            deadline = time.monotonic() + 10
            while not rec.converged() and time.monotonic() < deadline:
                rec.tick()
                time.sleep(0.05)
            assert rec.observed() == 1 and rec.converged()
            assert rec.state()["retired"] == [1, 2]
        finally:
            _close_all(servers, src)

    def test_desired_clamped_to_floors(self, tel):
        servers = []
        spawn = _inproc_spawner(servers)
        src = ProcessHTTPSource(workers=[spawn(0, None)])
        rec = FleetReconciler(src, 1, spawn=spawn, min_workers=1,
                              max_workers=3)
        try:
            assert rec.set_desired(99) == 3
            assert rec.set_desired(0) == 1
        finally:
            _close_all(servers, src)

    def test_killed_worker_reconciled_into_same_lineage(self, tel):
        """kill -9 equivalent: the worker dies hard; the reconciler's
        embedded supervisor relaunches it into the SAME slot on the
        SAME ports — the serving fleet's rendezvous lineage."""
        servers = []
        spawn = _inproc_spawner(servers)
        src = ProcessHTTPSource(workers=[spawn(0, None), spawn(1, None)])
        rec = FleetReconciler(src, 2, spawn=spawn,
                              probe_interval=0.05)
        rec.supervisor.probe_timeout = 0.5
        rec.supervisor.restart_backoff = 0.05
        port0 = src.workers[0].port
        try:
            servers[0].close()                # hard kill
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                rec.tick()
                if src.workers[0].alive and len(servers) >= 3:
                    break
                time.sleep(0.05)
            assert src.workers[0].alive
            assert src.workers[0].port == port0   # same lineage
            assert rec.observed() == 2
        finally:
            _close_all(servers, src)

    def test_grow_after_shrink_resurrects_retired_slot(self, tel):
        servers = []
        spawn = _inproc_spawner(servers)
        src = ProcessHTTPSource(workers=[spawn(0, None), spawn(1, None)])
        rec = FleetReconciler(src, 2, spawn=spawn, max_workers=3)
        try:
            rec.set_desired(1)
            deadline = time.monotonic() + 10
            while not rec.converged() and time.monotonic() < deadline:
                rec.tick()
                time.sleep(0.05)
            assert src.workers[1].retired
            port1 = src.workers[1].port
            rec.set_desired(2)
            rec.tick()
            assert rec.observed() == 2
            assert len(src.workers) == 2      # slot reused, not appended
            assert src.workers[1].port == port1
            assert not src.workers[1].retired
        finally:
            _close_all(servers, src)

    @pytest.mark.chaos
    def test_chaos_spawn_site_retries_next_tick(self, tel):
        faults.configure("fleet.spawn:error:1.0:0:1", seed=0)
        servers = []
        spawn = _inproc_spawner(servers)
        src = ProcessHTTPSource(workers=[spawn(0, None)])
        rec = FleetReconciler(src, 1, spawn=spawn, max_workers=2)
        try:
            rec.set_desired(2)
            rec.tick()                        # spawn faulted
            assert rec.observed() == 1
            assert rec.state()["last_error"] is not None
            assert _counter_total(
                "mmlspark_autoscale_spawn_failures") == 1
            rec.tick()                        # budget spent: clean spawn
            assert rec.observed() == 2
            assert rec.state()["last_error"] is None
        finally:
            _close_all(servers, src)

    @pytest.mark.chaos
    def test_chaos_drain_site_retries_next_tick(self, tel):
        faults.configure("fleet.drain:error:1.0:0:1", seed=0)
        servers = []
        spawn = _inproc_spawner(servers)
        src = ProcessHTTPSource(workers=[spawn(0, None), spawn(1, None)])
        rec = FleetReconciler(src, 2, spawn=spawn)
        try:
            rec.set_desired(1)
            rec.tick()                        # drain POST faulted
            assert not src.workers[1].draining
            deadline = time.monotonic() + 10
            while not rec.converged() and time.monotonic() < deadline:
                rec.tick()                    # retried clean
                time.sleep(0.05)
            assert rec.observed() == 1 and src.workers[1].retired
            assert _counter_total("mmlspark_faults_injected_total") >= 1
        finally:
            _close_all(servers, src)


# ------------------------------------------------- drain semantics (fleet)

class TestGracefulDrain:
    def test_draining_worker_sheds_then_retires_empty(self, tel):
        servers = []
        spawn = _inproc_spawner(servers)
        src = ProcessHTTPSource(workers=[spawn(0, None)])
        loop = ReplayServingLoop(src, _Echo()).start()
        try:
            url = src.workers[0].url
            assert _post(url, b"before")[0] == 200
            src.beginDrain(0)
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(url, b"rejected")
            assert ei.value.code == 503
            assert "Retry-After" in ei.value.headers
            assert "draining" in ei.value.read().decode()
            deadline = time.monotonic() + 10
            while (not src.drainComplete(0)
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            assert src.drainComplete(0)
            src.retireWorker(0)
            assert src.workers[0].retired and src.aliveCount() == 0
        finally:
            loop.stop()
            _close_all(servers, src)

    def test_inflight_exchange_survives_drain(self, tel):
        """The zero-loss guarantee: a request admitted BEFORE the drain
        gets its reply even though the drain begins while it is queued."""
        servers = []
        spawn = _inproc_spawner(servers)
        src = ProcessHTTPSource(workers=[spawn(0, None)])
        try:
            url = src.workers[0].url
            results = {}
            t = threading.Thread(target=lambda: results.update(
                r=_post(url, b"admitted", timeout=15)))
            t.start()
            deadline = time.monotonic() + 5
            while (servers[0].source.inflight() == 0
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            src.beginDrain(0)                 # drain with one in flight
            assert not src.drainComplete(0)   # the admitted row blocks it
            loop = ReplayServingLoop(src, _Echo()).start()
            try:
                t.join(timeout=15)
                assert results["r"][0] == 200
                assert json.loads(results["r"][1])["echo"] == "admitted"
                deadline = time.monotonic() + 10
                while (not src.drainComplete(0)
                       and time.monotonic() < deadline):
                    time.sleep(0.05)
                assert src.drainComplete(0)
            finally:
                loop.stop()
        finally:
            _close_all(servers, src)


# --------------------------------------- Retry-After from burn severity

class TestRetryAfterSeverity:
    def _engine(self, burn_fast):
        eng = _slo_latency(TimeSeriesSampler(interval=1.0))
        with eng._lock:
            eng._states["p99-latency"] = "breach"
            eng._last = {"p99-latency": {"state": "breach",
                                         "burn_fast": burn_fast,
                                         "burn_slow": burn_fast}}
        return eng

    def test_retry_after_scales_with_fast_burn(self):
        assert self._engine(1.2).retry_after() == 2     # ceil(1.2)
        assert self._engine(7.0).retry_after() == 7
        assert self._engine(200.0).retry_after() == 30  # capped
        assert self._engine(float("inf")).retry_after() == 30
        eng = _slo_latency(TimeSeriesSampler(interval=1.0))
        assert eng.retry_after() == 1                   # nothing burning

    def test_shed_503_carries_derived_retry_after(self, tel):
        eng = self._engine(7.0)
        src = HTTPSource(max_queue_depth=8, slo=eng)
        try:
            assert eng.should_shed()
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(src.url, b"x")
            assert ei.value.code == 503
            assert ei.value.headers["Retry-After"] == "7"
        finally:
            src.close()


# ------------------------------------- fleet-level healthz (driver probe)

class TestFleetHealthz:
    def test_driver_healthz_aggregates_workers_and_control_plane(
            self, tel, tmp_path):
        servers = []
        spawn = _inproc_spawner(servers)
        src = ProcessHTTPSource(workers=[spawn(0, None), spawn(1, None)])
        rec = FleetReconciler(src, 2, spawn=spawn)
        sampler = TimeSeriesSampler(interval=1.0)
        slo = _slo_latency(sampler)
        asc = ServingAutoscaler(slo, rec, grow_window=1.0)
        driver = HTTPSource(name="fleet-driver")
        driver.fleet_state = lambda: fleet_doc(src, asc, rec)
        try:
            code, h = _get_json(driver.url.rstrip("/") + "/healthz")
            assert code == 200 and h["ok"] is True
            fleet = h["fleet"]
            assert fleet["workers_alive"] == 2
            assert set(fleet["workers"]) == {"0", "1"}
            for w in fleet["workers"].values():
                assert w["state"] == "alive"
                assert w["queue_depth"] == 0 and w["inflight"] == 0
                assert isinstance(w["breakers"], dict)
            assert fleet["autoscale"]["desired"] == 2
            assert fleet["autoscale"]["objectives"] == ["p99-latency"]
            assert fleet["reconciler"]["converged"] is True
            # a dead worker flips the aggregated ok
            servers[0].close()
            src.markWorkerDead(0, reason="test")
            code, h = _get_json(driver.url.rstrip("/") + "/healthz")
            assert h["ok"] is False
            assert h["fleet"]["workers"]["0"]["state"] == "dead"
        finally:
            driver.close()
            _close_all(servers, src)


# ------------------------------------------------------ chaos-serve bench

class TestChaosServeBench:
    def test_open_loop_accepts_url_callable(self):
        import bench_serving
        # a 0-length schedule exercises the callable-url plumbing
        # without a server round-trip
        out = bench_serving.run_open_loop(
            lambda: "http://127.0.0.1:1/", b"x",
            np.asarray([]), deadline=0.1, pool=2)
        assert out["offered"] == 0 and out["good"] == 0


# -------------------------------------------------- the chaos e2e (tier-1)

_CFG = {"type": "mlp", "hidden": [8], "num_classes": 3}
_ROW = (6,)


@pytest.fixture(scope="module")
def tiny_params():
    module = build_model(_CFG)
    return module.init(jax.random.PRNGKey(0),
                       np.zeros((1,) + _ROW, np.float32))


def _bundle(tmp_path, params):
    from mmlspark_tpu.io.serving import (BucketPolicy, FusedServingStep,
                                         save_bundle)
    step = FusedServingStep(
        _CFG, params, policy=BucketPolicy(max_batch=16, min_bucket=8),
        row_shape=_ROW, in_dtype=np.float32, output="argmax")
    save_bundle(str(tmp_path), step)
    return step


@pytest.mark.chaos
def test_elastic_serving_fleet_chaos_e2e(tel, tiny_params, tmp_path):
    """The acceptance scenario, in-process: under an open-loop bursty
    load a latency breach GROWS the fleet (the new worker comes up warm
    from the AOT bundle — zero compiles), a hard-killed worker is
    reconciled back into the same lineage (same ports, still warm), a
    throttled straggler worker keeps its clients served by retries, and
    sustained idle SHRINKS the fleet by graceful drain — zero lost
    replies across the whole scenario, and the driver /healthz flips
    back to ok."""
    _bundle(tmp_path, tiny_params)
    compiles_before = _counter_total("mmlspark_profiler_compiles")
    assert compiles_before >= 2           # the bundle build compiled

    servers = []
    spawn = _inproc_spawner(servers, bundle=str(tmp_path))
    src = ProcessHTTPSource(workers=[spawn(0, None)])
    assert servers[0].step.compiles() == 0    # launch replica is warm

    # the SLO engine watches the shared in-process registry: a tiny
    # threshold makes every served request count against the latency
    # budget, so the objective burns exactly while traffic flows
    sampler = TimeSeriesSampler(interval=0.1)
    slo = _slo_latency(sampler, fast=0.6, slow=1.2, threshold=1e-6)
    sampler.start()
    rec = FleetReconciler(src, 1, spawn=spawn, min_workers=1,
                          max_workers=2, interval=0.05,
                          probe_interval=0.05,
                          drain_timeout=15.0).start()
    rec.supervisor.probe_timeout = 0.5
    rec.supervisor.restart_backoff = 0.05
    asc = ServingAutoscaler(slo, rec, grow_window=0.3,
                            shrink_window=1.5, cooldown=1.0,
                            idle_rows_per_worker=0.5,
                            interval=0.1).start()
    driver = HTTPSource(name="fleet-driver")
    driver.fleet_state = lambda: fleet_doc(src, asc, rec)

    payload = base64.b64encode(
        np.zeros(_ROW, np.float32).tobytes())
    stop = threading.Event()
    ok, bad = [], []
    lock = threading.Lock()

    def client(ci):
        policy = RetryPolicy(name="test.e2e.client", max_attempts=80,
                             base_delay=0.05, max_delay=0.4,
                             deadline=30.0, seed=ci)
        while not stop.is_set():
            urls = src.urls
            if not urls:
                time.sleep(0.05)
                continue
            try:
                code, body = policy.run(lambda a, u=urls: _post(
                    u[(ci + a) % len(u)], payload, timeout=3.0))
                with lock:
                    (ok if code == 200
                     and "label" in json.loads(body) else bad).append(
                        (code, body))
            except Exception as e:
                with lock:
                    bad.append(("error", repr(e)))
            time.sleep(0.02)

    threads = [threading.Thread(target=client, args=(ci,))
               for ci in range(4)]
    try:
        for t in threads:
            t.start()
        # phase 1: bursty traffic burns the latency objective -> GROW
        deadline = time.monotonic() + 20
        while rec.observed() < 2 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert rec.observed() == 2, \
            f"no grow under load: {asc.state()} / {rec.state()}"
        assert len(servers) >= 2
        # the grown worker came up WARM from the bundle: zero compiles
        # in its own step, and no process-wide compile since the build
        assert servers[-1].step.compiles() == 0
        assert _counter_total(
            "mmlspark_profiler_compiles") == compiles_before

        # phase 2: straggler — worker 0 slows down (injected delay on
        # its serving path keeps it alive-but-slow); clients retry onto
        # the healthy replica and nothing is lost
        faults.configure("serving.batch:delay:0.5:0.2", seed=0)
        time.sleep(0.5)

        # phase 3: kill -9 one worker under load -> reconciled back
        # into the same lineage, still warm
        faults.clear()
        kill_port = src.workers[0].port
        n_servers = len(servers)
        servers[0].close()
        deadline = time.monotonic() + 20
        while (len(servers) == n_servers or not src.workers[0].alive) \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        assert src.workers[0].alive, "killed worker never reconciled"
        assert src.workers[0].port == kill_port   # same lineage
        assert servers[-1].step.compiles() == 0   # relaunched warm
        time.sleep(0.3)                           # traffic on the fresh one

        # phase 4: stop traffic -> burn recovers, sustained idle SHRINKS
        # the fleet to min_workers by graceful drain
        stop.set()
        for t in threads:
            t.join(timeout=30)
        deadline = time.monotonic() + 25
        while not (rec.observed() == 1 and rec.converged()) \
                and time.monotonic() < deadline:
            time.sleep(0.1)
        assert rec.observed() == 1 and rec.converged(), \
            f"no shrink at idle: {asc.state()} / {rec.state()}"
        retired = [wi for wi, w in enumerate(src.workers) if w.retired]
        assert len(retired) == 1

        # zero lost replies across grow/kill/straggler/shrink
        assert not bad, f"{len(bad)} lost/failed requests, e.g. {bad[0]}"
        assert len(ok) > 20
        assert _counter_total(
            "mmlspark_profiler_compiles") == compiles_before

        # /healthz flips back to ok once the fleet is calm + converged
        deadline = time.monotonic() + 15
        h = None
        while time.monotonic() < deadline:
            _code, h = _get_json(driver.url.rstrip("/") + "/healthz")
            if h["ok"]:
                break
            time.sleep(0.2)
        assert h is not None and h["ok"] is True, h
        assert h["fleet"]["workers_alive"] == 1
        assert h["fleet"]["autoscale"]["last_verdict"] == "shrink"
        verd = telemetry.snapshot()[
            "mmlspark_autoscale_verdicts"]["series"]
        kinds = {tuple(sorted(s["labels"].items()))[0][1]: s["value"]
                 for s in verd}
        assert kinds.get("grow", 0) >= 1 and kinds.get("shrink", 0) >= 1
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
        asc.stop()
        rec.stop()
        sampler.stop()
        driver.close()
        _close_all(servers, src)
