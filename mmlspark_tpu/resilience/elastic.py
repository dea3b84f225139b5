"""Elastic multi-host training: heartbeats, death verdicts, re-mesh, resume.

PR 3's resilience machinery (RetryPolicy, faults, FleetSupervisor, step
checkpoints) protects *serving*; training still died with its first lost
host. This module extends the same model to ``TpuLearner.fit``: a fit that
loses a host **re-meshes over the survivors and resumes from the latest
consensus checkpoint**, losing zero committed steps — the fault-tolerant
distributed-training posture of the reference's distributed LightGBM
lineage (PAPER.md L5), rebuilt on XLA collectives, and the
barrier-execution recovery shape of JAMPI (arxiv 2007.01811: a failed
collective stage re-runs from its barrier, here the checkpoint).

Three pieces:

* :class:`HostHeartbeat` — one per host, a background thread writing
  ``hb_<host>.json`` (atomic write-then-rename, like checkpoints) into a
  directory on the job's shared storage every ``interval`` seconds, carrying
  the host's latest committed ``(epoch, step)``. A host that stops beating
  *is* the failure signal — a preempted VM cannot be asked.
* :class:`TrainSupervisor` — the :class:`~.supervisor.FleetSupervisor`
  sibling for training fleets. Probes heartbeat ages (fault site
  ``supervisor.heartbeat``), declares a host dead once its heartbeat is
  older than the ``grace`` window, and answers the restart-vs-shrink
  question: **shrink** while the survivors still satisfy ``min_hosts``,
  **restart** (give up in-job, let the launcher relaunch against the same
  checkpointDir) below it.
* :class:`ElasticFitCoordinator` — drives ``learner._fit_core`` in a
  recovery loop. Every optimizer step passes through
  :meth:`ElasticStepContext.check_step` (fault site ``elastic.step``;
  transient errors ride the trainer's existing retry-once policy); a death
  verdict on a mesh member raises :class:`HostLossError` out of the step
  loop, and the coordinator then re-meshes (fault site ``elastic.remesh``):
  rebuilds the device pool from the surviving hosts, re-creates the
  ``parallel/mesh`` mesh, re-places params, and re-enters the fit — which
  resumes from the ``(epoch, step)`` consensus checkpoint
  (``checkpointEverySteps`` format), so every step that reached a
  checkpoint survives the loss bit-exactly.

The loop also closes the other way — **in-job grow**: a relaunched host
writes its heartbeat with a ``joining`` flag; the supervisor turns
sustained freshness through the *rejoin grace* window into a **grow
verdict** (fault site ``supervisor.rejoin``), and the coordinator admits
the joiner at the next committed **checkpoint boundary**
(:class:`HostRejoinError` unwinds the step loop exactly like a loss,
pointed the other way), re-meshing over survivors + joiner with
``max_hosts`` capping the pool — replays only, no fleet restart. The
JAMPI barrier-execution shape again: the consensus checkpoint is the
barrier a gang-scheduled re-entry targets.

Single-process mode rehearses the full recovery path with *simulated*
hosts (contiguous device groups, ``mesh.host_device_groups``): killing a
group's heartbeat exercises verdict -> re-mesh -> resume exactly as a real
preemption would (and :meth:`ElasticFitCoordinator.relaunch_host` the
grow half), which is what the tier-1 chaos tests drive. Multi-process mode runs the same
heartbeats and verdicts, but an in-job re-mesh is impossible once
``jax.distributed`` has lost a member — there the coordinator's job is to
fail FAST and cleanly (HostLossError instead of a hung collective), so the
launcher can relaunch the fleet smaller against the same checkpointDir;
the consensus-resume logic picks it up from the last committed step.

Beyond loss and grow, the fleet is **proactive**: heartbeat docs carry a
monotonic ``seq`` counter, so every freshness verdict compares
reader-observed seq advancement against the reader's own monotonic clock
(one skewed wall clock can neither kill a healthy host nor keep a ghost);
sustained straggler verdicts from the rolling-MAD detector are promoted
(``evict_after`` consecutive flags, ``min_hosts`` floor, never the
coordinator host) into an **evict** at the next committed checkpoint
boundary — the slow host is dropped *before* it fails, replays only, and
rejoins through the grow path once recovered. REAL multi-process fleets
re-enter the same fit through ``parallel/distributed``'s
RendezvousCoordinator: coordinator-service restart on the surviving
lowest-rank host, generation-stamped membership, barrier re-entry — a
kill -9'd process relaunches and joins the running fit instead of
forcing a full-size relaunch.

Env knobs: ``MMLSPARK_TPU_ELASTIC_GRACE`` (death-verdict window, seconds;
the ``elasticGraceSeconds`` param overrides), ``MMLSPARK_TPU_ELASTIC_HB``
(heartbeat write interval, default grace/4), ``MMLTPU_REJOIN_TIMEOUT``
(how long a below-quorum fleet waits for rejoining hosts).
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Callable, Optional

from .. import telemetry
from ..core.utils import get_logger
from . import faults
from .policy import default_transient

log = get_logger("resilience.elastic")

_m_host_losses = telemetry.registry.counter(
    "mmlspark_elastic_host_losses_total",
    "hosts declared dead by the train supervisor", labels=("host",))
_m_remeshes = telemetry.registry.counter(
    "mmlspark_elastic_remeshes_total",
    "fit recoveries that rebuilt the mesh over surviving hosts")
_m_attempt_failures = telemetry.registry.counter(
    "mmlspark_elastic_attempt_failures_total",
    "elastic fit attempts that ended in a classified-transient failure "
    "without a host verdict (retried on the same mesh)")
_m_recovery_seconds = telemetry.registry.histogram(
    "mmlspark_elastic_recovery_seconds",
    "host-loss detection -> first optimizer step committed on the "
    "re-meshed (or retried) fit")
_m_hosts_alive = telemetry.registry.gauge(
    "mmlspark_elastic_hosts_alive",
    "hosts currently alive in the elastic training fleet")
_m_steps_replayed = telemetry.registry.counter(
    "mmlspark_elastic_steps_replayed_total",
    "committed-but-unchekpointed steps re-run after a resume (the work a "
    "smaller checkpointEverySteps would have saved)")
_m_stragglers = telemetry.registry.counter(
    "mmlspark_elastic_stragglers_total",
    "hosts flagged anomalously slow by the rolling-MAD step-time "
    "detector (each flag episode counts once)", labels=("host",))
_m_rejoins = telemetry.registry.counter(
    "mmlspark_elastic_rejoins_total",
    "grow verdicts: relaunched hosts whose joining heartbeat stayed "
    "fresh through the rejoin grace window", labels=("host",))
_m_grows = telemetry.registry.counter(
    "mmlspark_elastic_grows_total",
    "fit recoveries that re-meshed the fleet LARGER (joiners admitted "
    "at a checkpoint boundary)")
_m_grow_recovery_seconds = telemetry.registry.histogram(
    "mmlspark_elastic_grow_recovery_seconds",
    "grow re-mesh start -> first optimizer step committed on the grown "
    "mesh (the cost of admitting a rejoined host)")
_m_heartbeat_errors = telemetry.registry.counter(
    "mmlspark_elastic_heartbeat_errors_total",
    "heartbeat writes that exhausted their retry budget (shared-FS "
    "trouble; the beacon thread stays alive and keeps trying)",
    labels=("host",))
_m_evictions = telemetry.registry.counter(
    "mmlspark_elastic_evictions_total",
    "proactive straggler EVICTIONS: hosts dropped from the mesh at a "
    "checkpoint boundary after sustaining straggler verdicts for "
    "evict_after consecutive passes (alive but slow; eligible to "
    "rejoin through the grow path once recovered)", labels=("host",))


class HostLossError(RuntimeError):
    """A mesh-member host was declared dead mid-fit. Deliberately NOT a
    ConnectionError: the per-step retry policy must not absorb it — the
    recovery is a re-mesh + checkpoint resume, not a redispatch."""

    def __init__(self, hosts):
        self.hosts = sorted(hosts)
        super().__init__(f"host(s) {', '.join(self.hosts)} declared dead "
                         f"mid-fit")


class HostEvictError(RuntimeError):
    """A sustained-straggler host earned an EVICT verdict and a
    checkpoint boundary has committed since: the step loop unwinds so
    the coordinator can re-mesh WITHOUT the slow host — the same unwind
    mechanism a host loss uses, fired *before* the host fails instead of
    after. The evicted host stays alive; once it recovers it rejoins
    through the ordinary joining-heartbeat grow path. Deliberately not a
    ConnectionError: the per-step retry must not absorb it."""

    def __init__(self, hosts):
        self.hosts = sorted(hosts)
        super().__init__(f"host(s) {', '.join(self.hosts)} evicted as "
                         f"sustained stragglers at checkpoint boundary")


class RendezvousPending(RuntimeError):
    """Multi-process fleets: the leader committed a rendezvous proposal
    whose ``unwind_at`` boundary this process has now reached — unwind
    the step loop and join the new generation. The deterministic unwind
    point (every process raises after the SAME committed step) is what
    keeps a grow/evict re-mesh from stranding peers mid-collective."""

    def __init__(self, generation: int):
        self.generation = generation
        super().__init__(f"rendezvous generation {generation} pending")


class HostRejoinError(RuntimeError):
    """A relaunched host earned a grow verdict and a checkpoint boundary
    has committed since: the step loop unwinds so the coordinator can
    re-mesh over survivors + joiner. NOT an error condition — it is the
    same unwind mechanism a host loss uses, pointed the other way (the
    fleet gets bigger). Deliberately not a ConnectionError: the per-step
    retry must not absorb it."""

    def __init__(self, hosts):
        self.hosts = sorted(hosts)
        super().__init__(f"host(s) {', '.join(self.hosts)} rejoining "
                         f"at checkpoint boundary")


class ElasticFleetLost(RuntimeError):
    """Survivors fell below ``min_hosts`` (or the failure budget ran out):
    in-job recovery is off the table; relaunch the fleet against the same
    checkpointDir to resume."""


def _grace_default() -> float:
    try:
        return float(os.environ.get("MMLSPARK_TPU_ELASTIC_GRACE", "") or 2.0)
    except ValueError:
        return 2.0


def _hb_interval_default(grace: float) -> float:
    try:
        v = os.environ.get("MMLSPARK_TPU_ELASTIC_HB", "")
        return float(v) if v else max(0.05, grace / 4.0)
    except ValueError:
        return max(0.05, grace / 4.0)


def heartbeat_dir(checkpoint_dir: str) -> str:
    return os.path.join(checkpoint_dir, "heartbeats")


# ---- fleet-health surface (GET /healthz) -----------------------------------
# The serving stack's /healthz shows breakers/queue/uptime; an operator
# watching an elastic fit could previously only see fleet state by
# scraping metrics. The active coordinator registers here and every
# /healthz payload (serving server + fleet workers) embeds the snapshot.

_fleet_lock = threading.Lock()
_fleet = None                        # guarded-by: _fleet_lock


def _register_fleet(coord):
    global _fleet
    with _fleet_lock:
        _fleet = coord


def _unregister_fleet(coord):
    global _fleet
    with _fleet_lock:
        if _fleet is coord:
            _fleet = None


def fleet_health():
    """The active elastic fleet's state for ``GET /healthz`` (None when
    no elastic fit is running in this process): hosts alive/dead, the
    straggler set, pending evict/grow verdicts, and the current
    rendezvous generation."""
    with _fleet_lock:
        coord = _fleet
    if coord is None:
        return None
    sup = coord.supervisor
    alive = sup.alive_hosts()
    return {
        "hosts_alive": len(alive),
        "alive": alive,
        "dead": sorted(sup.dead_hosts()),
        "stragglers": sorted(sup.straggler_hosts()),
        "pending_evict": sorted(sup.evict_verdicts()),
        "pending_grow": sorted(sup.joining_hosts()),
        "mesh_hosts": sorted(coord._mesh_hosts),
        "rendezvous_generation": (coord._rdzv.generation
                                  if coord._rdzv is not None else 0),
    }


class HostHeartbeat:
    """Background liveness beacon for one host.

    Writes ``hb_<host>.json`` with ``{host, seq, time, epoch, step}``
    every ``interval`` seconds (write-then-rename: a torn read must never
    look like a dead host). ``seq`` is a per-beacon monotonic counter —
    the freshness signal readers actually trust: a verdict compares
    *reader-observed seq advancement* against the reader's own monotonic
    clock, so one host with a skewed wall clock can neither be falsely
    declared dead nor kept alive as a ghost. ``time`` stays in the doc as
    informational metadata (and the same-writer deltas the straggler
    detector consumes, which no cross-host skew can distort).
    ``beat(epoch, step)`` advances the progress the file carries;
    :meth:`kill` stops the thread WITHOUT a farewell write — the
    simulated-preemption switch chaos tests flip (a real preemption stops
    mid-air the same way); :meth:`throttle` makes the carried progress
    advance only every k-th beat — the simulated-STRAGGLER switch (the
    host is alive and beating, just slow)."""

    def __init__(self, host_id: str, directory: str, interval: float,
                 joining: bool = False):
        from .policy import RetryPolicy
        self.host_id = host_id
        self.directory = directory
        self.interval = interval
        self._lock = threading.Lock()
        self._pos = (0, -1)          # guarded-by: _lock
        self._joining = joining      # guarded-by: _lock
        self._seq = 0                # guarded-by: _lock
        self._generation = 0         # guarded-by: _lock
        self._throttle = 1           # guarded-by: _lock
        self._beats = 0              # guarded-by: _lock
        self._stop = threading.Event()
        # transient shared-FS hiccups must not silence the beacon — a
        # silent beacon IS a death verdict. Retry each write; exhaustion
        # is counted and survived (the next interval tries again).
        self._retry = RetryPolicy(name="elastic.heartbeat", max_attempts=3,
                                  base_delay=min(0.05, interval / 4),
                                  max_delay=max(0.05, interval / 2),
                                  retryable=lambda e: isinstance(
                                      e, (OSError, ValueError)))
        self._thread = threading.Thread(
            target=self._run, daemon=True, name=f"heartbeat-{host_id}")

    @property
    def path(self) -> str:
        return os.path.join(self.directory, f"hb_{self.host_id}.json")

    def beat(self, epoch: int, step: int):
        with self._lock:
            self._beats += 1
            if self._throttle <= 1:
                self._pos = (epoch, step)
            elif self._beats % self._throttle == 0:
                # simulated straggler: the carried position advances ONE
                # step per k real beats (never jumps to the true step),
                # so heartbeat-derived seconds-per-step reads k times the
                # fleet cadence — the signature of a genuinely slow host
                pe, ps = self._pos
                self._pos = (epoch, ps + 1 if epoch == pe else 0)

    def throttle(self, every: int):
        """Simulated straggler: carried progress advances only every
        ``every``-th :meth:`beat` (1 = healthy). The beacon keeps
        beating — a straggler is alive — but its seconds-per-step, as
        derived from heartbeat progress, multiplies by ``every``."""
        with self._lock:
            self._throttle = max(1, int(every))

    def set_joining(self, joining: bool):
        """Flip the rejoin flag and publish it IMMEDIATELY (best
        effort): a stale ``joining`` doc lingering for one beat interval
        after the host was admitted would read as a relaunch
        self-report and re-kill the freshly admitted member."""
        with self._lock:
            self._joining = joining
        try:
            self._write()
        except OSError:
            pass    # the beacon thread retries within one interval

    def set_generation(self, generation: int):
        """Stamp the rendezvous generation this host currently belongs
        to into its heartbeat (multi-process fleets): operators and the
        supervisor can see which incarnation each host last joined."""
        with self._lock:
            self._generation = int(generation)

    def _write(self):
        with self._lock:
            self._seq += 1
            (epoch, step), joining = self._pos, self._joining
            seq, generation = self._seq, self._generation
        doc = {"host": self.host_id, "seq": seq, "time": time.time(),
               "epoch": epoch, "step": step}
        if generation:
            doc["generation"] = generation
        if joining:
            doc["joining"] = True
        # unique tmp per writer thread: set_joining publishes from the
        # caller's thread while the beacon thread keeps beating.
        # No fsync before the rename ON PURPOSE: a heartbeat needs READ
        # atomicity (rename gives it), not crash durability — a host
        # that crashes SHOULD look dead, and an fsync per beat would
        # hammer the shared filesystem the beacon must never stall on.
        tmp = f"{self.path}.tmp.{threading.get_ident()}"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(doc, f)
        # graftlint: disable=protocol-rename-before-fsync
        os.replace(tmp, self.path)

    def _run(self):
        while not self._stop.is_set():
            try:
                self._retry.run(lambda _a: self._write())
            except Exception as e:   # exhausted: count, survive, retry
                _m_heartbeat_errors.labels(host=self.host_id).inc()
                log.warning("heartbeat %s write failed after retries: %s",
                            self.host_id, e)
            self._stop.wait(self.interval)

    def start(self) -> "HostHeartbeat":
        os.makedirs(self.directory, exist_ok=True)
        self._write()
        self._thread.start()
        return self

    def stop(self):
        """Clean shutdown (fit finished): final write then join, so a
        supervisor that outlives the fit doesn't read a stale file age."""
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=2)

    def kill(self):
        """Simulated preemption: the beacon stops mid-air, no final write.
        The supervisor's grace window turns the silence into a verdict."""
        self._stop.set()


class TrainSupervisor:
    """Death-verdict loop over an elastic training fleet's heartbeats.

    The :class:`~.supervisor.FleetSupervisor` sibling: same tick/thread
    shape, but the subjects are training hosts (heartbeat files on shared
    storage) rather than serving workers (HTTP health probes), and the
    remedy is a re-mesh rather than a respawn — dead training hosts are
    *removed*, not restarted, because the collective program must shrink
    with them.

    ``probe(host_id) -> age_seconds | None`` is pluggable (tests inject
    clocks); the default reads the heartbeat file's ``time`` field. A host
    whose heartbeat is older than ``grace`` — or unreadable past the same
    window — is declared dead exactly once; verdicts are sticky (a zombie
    heartbeat resuming after its verdict stays dead: its devices left the
    mesh, rejoining means relaunching).
    """

    def __init__(self, host_ids, directory: str,
                 grace: Optional[float] = None,
                 min_hosts: int = 1,
                 probe: Optional[Callable] = None,
                 probe_interval: Optional[float] = None,
                 anomaly_detector=None,
                 rejoin_grace: Optional[float] = None,
                 evict_after: int = 0,
                 self_host: Optional[str] = None):
        from ..telemetry.slo import StepTimeAnomalyDetector
        self.host_ids = list(host_ids)
        self.directory = directory
        #: this process's own host id on a REAL fleet (None in the
        #: single-process simulation where every host is "us"): a
        #: running process is self-evidently alive, so the death pass
        #: skips it — its own heartbeat doc lagging (fs hiccup, stale
        #: joining flag from its rejoin) must not produce a self-verdict
        self.self_host = self_host
        self.grace = grace if grace is not None else _grace_default()
        self.min_hosts = max(1, min_hosts)
        #: consecutive straggler-flagged passes that promote the advisory
        #: verdict into an EVICT verdict (0 = advisory only, never evict)
        self.evict_after = max(0, int(evict_after))
        #: how long a relaunched host's ``joining`` heartbeat must stay
        #: fresh before the GROW verdict lands (its own window, symmetric
        #: to the death grace: a flapping relauncher must not churn the
        #: mesh). Default: the death grace.
        self.rejoin_grace = (rejoin_grace if rejoin_grace is not None
                             else self.grace)
        self._probe = probe or self._probe_file
        self.probe_interval = (probe_interval if probe_interval is not None
                               else max(0.05, self.grace / 4.0))
        #: rolling-MAD step-time detector fed from heartbeat progress; a
        #: STRAGGLER verdict (consistently slow, still beating) is advisory
        #: — reported, never a death verdict (pass anomaly_detector=False
        #: to disable, or inject a configured detector)
        self.anomaly = (StepTimeAnomalyDetector()
                        if anomaly_detector is None
                        else (anomaly_detector or None))
        self._lock = threading.Lock()
        self._dead: set[str] = set()        # guarded-by: _lock
        self._joining: dict[str, float] = {}     # guarded-by: _lock
        self._join_seen: dict[str, float] = {}   # guarded-by: _lock
        self._progress: dict[str, tuple] = {}    # guarded-by: _lock
        self._flagged: set[str] = set()     # guarded-by: _lock
        # reader-observed freshness: host -> (last seq, monotonic instant
        # the reader first saw it). Death and grow verdicts compare seq
        # ADVANCEMENT against the reader's monotonic clock — writer
        # wall-clock skew cannot fake either direction.
        self._fresh: dict[str, tuple] = {}       # guarded-by: _lock
        self._join_fresh: dict[str, tuple] = {}  # guarded-by: _lock
        self._streak: dict[str, int] = {}        # guarded-by: _lock
        self._evict: dict[str, float] = {}       # guarded-by: _lock
        self._started_at = time.monotonic()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="train-supervisor")
        _m_hosts_alive.set(len(self.host_ids))

    # ---- probing ----
    def _read_doc(self, host_id: str) -> Optional[dict]:
        try:
            with open(os.path.join(self.directory,
                                   f"hb_{host_id}.json"),
                      "r", encoding="utf-8") as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def _doc_age(self, host_id: str, doc: dict,
                 table: dict) -> Optional[float]:
        """Reader-side freshness of one heartbeat doc: seconds since the
        doc's ``seq`` last ADVANCED, measured on the reader's monotonic
        clock (``table`` is the per-verdict-kind observation map). Docs
        written before the seq field existed fall back to the writer's
        wall time — legacy behavior, skew and all."""
        seq = doc.get("seq")
        if not isinstance(seq, int):
            try:
                return max(0.0, time.time() - float(doc["time"]))
            except (KeyError, TypeError, ValueError):
                return None
        now = time.monotonic()
        with self._lock:
            prev = table.get(host_id)
            if prev is None or prev[0] != seq:
                table[host_id] = (seq, now)
                return 0.0
            return now - prev[1]

    def _probe_file(self, host_id: str) -> Optional[float]:
        """Heartbeat age in seconds; None when the file is missing or
        unreadable (counted against the host once the startup grace is
        spent — a host that never wrote at all is as dead as one that
        stopped)."""
        doc = self._read_doc(host_id)
        if doc is None:
            return None
        age = self._doc_age(host_id, doc, self._fresh)
        if age is None:
            return None
        # an in-mesh host writing a JOINING heartbeat is a fresh process
        # self-reporting a restart (killed + relaunched inside the grace
        # window): its old membership — devices, collectives — is gone,
        # so the beating file must still produce a death verdict; the
        # grow path then readmits the new incarnation
        if doc.get("joining"):
            return float("inf")
        self._note_progress(host_id, doc)
        return age

    def _note_progress(self, host_id: str, doc: dict):
        """Feed the anomaly detector from heartbeat progress: successive
        probes of the same epoch yield (wall delta / steps advanced) — a
        central seconds-per-step estimate that needs no new wire format."""
        if self.anomaly is None:
            return
        try:
            cur = (int(doc["epoch"]), int(doc["step"]), float(doc["time"]))
        except (KeyError, TypeError, ValueError):
            return
        with self._lock:
            prev = self._progress.get(host_id)
            self._progress[host_id] = cur
        if prev is None:
            return
        pe, ps, pt = prev
        e, s, t = cur
        if e == pe and s > ps and t > pt:
            self.anomaly.observe(host_id, (t - pt) / (s - ps))

    def tick(self):
        """One verdict pass (public: deterministic tests drive it directly,
        the background thread calls it on ``probe_interval``)."""
        verdicts = []
        for host_id in self.host_ids:
            if host_id == self.self_host:
                continue
            with self._lock:
                if host_id in self._dead:
                    continue
            faults.inject("supervisor.heartbeat")
            age = self._probe(host_id)
            if age is None:
                # missing file: only fatal once the fleet has had time to
                # write its first beats
                if time.monotonic() - self._started_at < self.grace:
                    continue
                verdicts.append((host_id, None))
            elif age > self.grace:
                verdicts.append((host_id, age))
        for host_id, age in verdicts:
            with self._lock:
                if host_id in self._dead:
                    continue
                self._dead.add(host_id)
                alive = len(self.host_ids) - len(self._dead)
            # verdict bookkeeping is IO (log/trace/metrics): after release
            _m_host_losses.labels(host=host_id).inc()
            _m_hosts_alive.set(alive)
            telemetry.trace.instant("elastic/host_loss", host=host_id,
                                    age=age)
            telemetry.flight.note("elastic/host_loss", host=host_id,
                                  age=age, alive=alive)
            log.warning(
                "host %s declared DEAD (heartbeat %s, grace %.2fs); "
                "%d host(s) remain", host_id,
                "missing" if age is None else f"{age:.2f}s old",
                self.grace, alive)
        self._grow_pass()
        self._straggler_pass()

    def _grow_pass(self):
        """GROW verdicts — the death pass's mirror. A dead host whose
        heartbeat file is beating again WITH the ``joining`` flag is a
        relaunch (not a zombie: sticky death still holds for flagless
        resurrections); once it has stayed fresh through ``rejoin_grace``
        the host earns a grow verdict the coordinator can admit at the
        next checkpoint boundary. Verdict bookkeeping decided under the
        lock; IO after release."""
        with self._lock:
            candidates = [h for h in self._dead if h not in self._joining]
        verdicts = []
        for host_id in candidates:
            faults.inject("supervisor.rejoin")
            doc = self._read_doc(host_id)
            age = (self._doc_age(host_id, doc, self._join_fresh)
                   if doc is not None and doc.get("joining") else None)
            fresh = age is not None and age <= self.grace
            now = time.monotonic()
            with self._lock:
                if not fresh:
                    # stale or flagless: the relaunch flapped (or was a
                    # zombie); restart its window
                    self._join_seen.pop(host_id, None)
                    continue
                t0 = self._join_seen.setdefault(host_id, now)
                if now - t0 < self.rejoin_grace:
                    continue
                self._join_seen.pop(host_id, None)
                self._joining[host_id] = now
            verdicts.append(host_id)
        for host_id in verdicts:
            _m_rejoins.labels(host=host_id).inc()
            telemetry.trace.instant("elastic/rejoin", host=host_id)
            telemetry.flight.note("elastic/rejoin", host=host_id)
            log.warning("host %s earned a GROW verdict (joining heartbeat "
                        "fresh through the %.2fs rejoin window); eligible "
                        "to re-enter the mesh at the next checkpoint "
                        "boundary", host_id, self.rejoin_grace)

    def joining_hosts(self) -> dict:
        """Hosts holding a grow verdict -> verdict time (monotonic). The
        coordinator admits them at the next checkpoint boundary."""
        with self._lock:
            return dict(self._joining)

    def admit(self, host_id: str):
        """The coordinator admitted a rejoined host back into the mesh:
        clear its death verdict and grow state so the death pass watches
        it again."""
        with self._lock:
            self._dead.discard(host_id)
            self._joining.pop(host_id, None)
            self._join_seen.pop(host_id, None)
            self._join_fresh.pop(host_id, None)
            self._evict.pop(host_id, None)
            self._streak.pop(host_id, None)
            # re-baseline freshness: the readmitted host gets a full
            # grace window from its next observed beat
            self._fresh.pop(host_id, None)
            alive = len(self.host_ids) - len(self._dead)
        _m_hosts_alive.set(alive)

    def _straggler_pass(self):
        """Anomaly verdicts: flag hosts the rolling-MAD detector calls
        stragglers (and unflag recovered ones so a relapse re-flags).
        With ``evict_after`` > 0, a host flagged for that many
        CONSECUTIVE passes is promoted from advisory to an **EVICT
        verdict** — subject to the floors: the survivors after the evict
        must still satisfy ``min_hosts``, and the coordinator host
        (lowest-ranked alive — it owns checkpoints and rendezvous
        proposals) is never evicted. The verdict is consumed by the fit
        coordinator at the next committed checkpoint boundary. Flag
        bookkeeping is decided under the lock; the IO (metrics,
        instants, flight notes, logs) happens after release."""
        if self.anomaly is None:
            return
        current = self.anomaly.stragglers()
        evict_verdicts = []
        with self._lock:
            current -= self._dead
            newly = current - self._flagged
            self._flagged = current
            alive = [h for h in self.host_ids if h not in self._dead]
            now = time.monotonic()
            for h in list(self._streak):
                if h not in current:
                    self._streak.pop(h)
            for h in sorted(current):
                self._streak[h] = self._streak.get(h, 0) + 1
                if (self.evict_after > 0 and h not in self._evict
                        and self._streak[h] >= self.evict_after
                        and alive and h != min(alive)
                        and len(alive) - len(self._evict) - 1
                        >= self.min_hosts):
                    self._evict[h] = now
                    evict_verdicts.append(h)
        med = (self.anomaly.host_medians()
               if (newly or evict_verdicts) else {})
        for host_id in sorted(newly):
            _m_stragglers.labels(host=host_id).inc()
            telemetry.trace.instant("elastic/straggler", host=host_id,
                                    median_s=med.get(host_id))
            telemetry.flight.note("elastic/straggler", host=host_id,
                                  median_s=med.get(host_id))
            log.warning("host %s flagged as STRAGGLER (median step "
                        "%.4fs vs fleet %s); still alive — advisory only",
                        host_id, med.get(host_id, float("nan")),
                        {h: round(v, 4) for h, v in med.items()})
        for host_id in evict_verdicts:
            telemetry.trace.instant("elastic/evict", host=host_id,
                                    stage="verdict",
                                    median_s=med.get(host_id))
            telemetry.flight.note("elastic/evict", host=host_id,
                                  stage="verdict")
            log.warning(
                "host %s earned an EVICT verdict (straggler for %d "
                "consecutive passes, median step %.4fs); dropped at the "
                "next committed checkpoint boundary", host_id,
                self.evict_after, med.get(host_id, float("nan")))

    def evict_verdicts(self) -> dict:
        """Hosts holding an evict verdict -> verdict time (monotonic).
        The coordinator consumes them at the next committed checkpoint
        boundary through the same unwind path as a host loss."""
        with self._lock:
            return dict(self._evict)

    def mark_evicted(self, host_id: str):
        """The coordinator dropped an evicted host from the mesh: record
        the (sticky) death verdict and clear its straggler state — its
        samples are stale the moment it leaves the mesh, and a held flag
        would block the rejoin it is entitled to once recovered."""
        with self._lock:
            self._dead.add(host_id)
            self._evict.pop(host_id, None)
            self._streak.pop(host_id, None)
            self._flagged.discard(host_id)
            alive = len(self.host_ids) - len(self._dead)
        if self.anomaly is not None:
            self.anomaly.forget(host_id)
        _m_evictions.labels(host=host_id).inc()
        _m_hosts_alive.set(alive)

    def straggler_hosts(self) -> set[str]:
        """Hosts currently flagged anomalously slow (advisory — they are
        alive and beating, just burning the step-time budget)."""
        with self._lock:
            return set(self._flagged)

    def dead_hosts(self) -> set[str]:
        with self._lock:
            return set(self._dead)

    def alive_hosts(self) -> list[str]:
        with self._lock:
            return [h for h in self.host_ids if h not in self._dead]

    def decision(self) -> str:
        """``"shrink"`` when the survivors can keep training in-job,
        ``"restart"`` when they cannot (relaunch against the same
        checkpointDir — consensus resume carries the run over)."""
        return ("shrink" if len(self.alive_hosts()) >= self.min_hosts
                else "restart")

    def _run(self):
        while not self._stop.is_set():
            try:
                self.tick()
            except Exception as e:   # a probe bug must not kill the loop
                log.warning("train-supervisor tick failed: %s", e)
            self._stop.wait(self.probe_interval)

    def clear_stale_heartbeats(self):
        """Remove ``hb_*.json`` ghosts from a PREVIOUS run (not modified
        within the grace window): without this a supervisor starting
        against a reused checkpointDir reads last week's heartbeat and
        declares an instant death (or an instant zombie) before the
        relaunched fleet writes its first beat. Staleness is judged by
        the file's mtime — the filesystem's clock, not the dead writer's
        wall clock, so a ghost written by a skewed host still clears.
        Fresh files — this run's — are untouched."""
        try:
            names = os.listdir(self.directory)
        except OSError:
            return
        for name in names:
            if not (name.startswith("hb_") and name.endswith(".json")):
                continue
            path = os.path.join(self.directory, name)
            try:
                stale = time.time() - os.path.getmtime(path) > self.grace
            except OSError:
                stale = True     # unreadable ghosts go too
            if stale:
                try:
                    os.remove(path)
                    log.info("cleared stale heartbeat %s from a previous "
                             "run", name)
                except OSError:
                    pass

    def start(self) -> "TrainSupervisor":
        self.clear_stale_heartbeats()
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=5)


class ElasticStepContext:
    """The per-step hook the trainer's dispatch loop calls during an
    elastic fit. Cheap when nothing is wrong: one fault-site check and one
    set read per optimizer step."""

    def __init__(self, coordinator: "ElasticFitCoordinator"):
        self._coord = coordinator

    def check_step(self):
        """Runs inside the step dispatch, BEFORE the device work. An
        injected ``elastic.step`` fault is a ConnectionError — the
        trainer's retry-once policy absorbs singles, doubles escalate to
        the coordinator's transient classification. A death verdict on a
        mesh member raises :class:`HostLossError`; a grow verdict with a
        checkpoint boundary committed behind it raises
        :class:`HostRejoinError`; a sustained-straggler evict verdict
        with a boundary behind it raises :class:`HostEvictError` (all
        non-transient: they skip the retry and unwind to the
        coordinator's re-mesh)."""
        faults.inject("elastic.step")
        dead = self._coord.dead_mesh_hosts()
        if dead:
            raise HostLossError(dead)
        if self._coord._multiproc:
            # grow/evict re-meshes in a REAL fleet must unwind every
            # process at the same step — they go through the leader's
            # rendezvous proposal (check_rendezvous), never a unilateral
            # raise here; only a dead mesh member (collectives already
            # broken) justifies unwinding alone
            return
        grow = self._coord.pending_grow()
        if grow:
            raise HostRejoinError(grow)
        evict = self._coord.pending_evict()
        if evict:
            raise HostEvictError(evict)

    def step_committed(self, epoch: int, step: int):
        """The trainer reports each completed optimizer step: advances
        this process's heartbeat progress, closes any pending
        recovery-time measurement, and feeds the committed-step journal
        the chaos tests audit for gaps. Multi-process fleets also poll
        the rendezvous doc here — the deterministic unwind point: every
        process raises :class:`RendezvousPending` after the SAME
        committed step, so a grow/evict re-mesh never strands a peer
        mid-collective."""
        self._coord.note_step(epoch, step)
        self._coord.check_rendezvous(epoch, step)

    def checkpoint_saved(self, epoch: int, step: Optional[int]):
        """A checkpoint COMMITTED (rename + manifest durable — on the
        async path this fires from the writer thread strictly after the
        commit, never at submit). Checkpoint boundaries are where grow
        re-meshes become eligible: a joiner admitted here replays ~zero
        steps."""
        self._coord.note_checkpoint(epoch, step)

    def resumed(self, pos, params_digest: Optional[str]):
        """The trainer reports the checkpoint position (or None for a
        fresh start) and a digest of the restored params — the bit-exact
        resume evidence."""
        self._coord.note_resume(pos, params_digest)

    # ---- in-memory boosting-state candidates (elastic GBDT fits) ----
    def save_snapshot(self, state):
        """The GBDT engine's per-iteration boosting-state candidate
        (newest wins): host-side arrays a re-meshed attempt resumes
        from. Pair with :meth:`checkpoint_saved` so grow boundaries work
        for boosted fits too."""
        self._coord.snapshot = state

    def latest_snapshot(self):
        return self._coord.snapshot


class ElasticFitCoordinator:
    """Drives a ``TpuLearner`` fit through host loss.

    ``fit(df)``: build the host groups, start heartbeats + the
    supervisor, then loop ``learner._fit_core(df, devices=pool,
    elastic_ctx=ctx)`` until it returns a model. A
    :class:`HostLossError` (or an exhausted-transient failure that a
    fresh verdict pass attributes to a dead host) triggers the re-mesh:
    survivors' devices become the new pool, and the next ``_fit_core``
    attempt resumes from the latest consensus checkpoint. Failures with
    *no* dead host burn the ``max_failures`` budget and retry on the same
    mesh — persistent infrastructure trouble must not loop forever.
    """

    def __init__(self, learner=None, n_hosts: int = 0,
                 min_hosts: int = 1,
                 grace: Optional[float] = None,
                 max_failures: int = 5,
                 heartbeat_interval: Optional[float] = None,
                 max_hosts: int = 0,
                 rejoin_grace: Optional[float] = None,
                 checkpoint_dir: Optional[str] = None,
                 evict_after: int = 0):
        ckdir = checkpoint_dir or (learner.getCheckpointDir()
                                   if learner is not None else "")
        if not ckdir:
            raise ValueError(
                "elastic fit requires checkpointDir: recovery is a resume "
                "from the consensus checkpoint — without one a host loss "
                "restarts from scratch, losing every committed step")
        self.learner = learner
        self.checkpoint_dir = ckdir
        self.grace = grace if grace is not None else _grace_default()
        self.min_hosts = max(1, min_hosts)
        self.max_failures = max(1, max_failures)
        self._hb_interval = (heartbeat_interval
                             if heartbeat_interval is not None
                             else _hb_interval_default(self.grace))
        from ..parallel import distributed as dist
        from ..parallel import mesh as meshlib
        self._rdzv = dist.rendezvous_coordinator()
        if self._rdzv is not None:
            # rendezvous-armed multi-process fleet: membership is the
            # LAUNCH fleet (stable host ids = launch ranks), whatever
            # the current incarnation's size — a dropped host stays on
            # the watch list so its rejoin can be seen
            n_env = int(os.environ.get(dist.ENV_NUM_PROCESSES, "0") or 0)
            hosts = sorted(set(self._rdzv.ranks)
                           | {f"host{i}" for i in range(n_env)}
                           | {self._rdzv.host_id})
            self.groups = {h: [] for h in hosts}
        else:
            self.groups = dict(meshlib.host_device_groups(n_hosts))
        #: grow ceiling: the mesh never grows past this many hosts
        #: (0 = the launch fleet size)
        self.max_hosts = max_hosts or len(self.groups)
        self.hb_dir = heartbeat_dir(ckdir)
        self.heartbeats = {h: HostHeartbeat(h, self.hb_dir,
                                            self._hb_interval)
                           for h in self.groups}
        self.supervisor = TrainSupervisor(
            list(self.groups), self.hb_dir, grace=self.grace,
            min_hosts=self.min_hosts, rejoin_grace=rejoin_grace,
            evict_after=evict_after,
            self_host=(self._rdzv.host_id if self._rdzv is not None
                       else None))
        self.attempts: list[dict] = []   # per-attempt journal (tests/bench)
        self.committed: list[tuple] = []   # (epoch, step) journal
        self.snapshot = None   # GBDT boosting-state candidate (newest wins)
        self._mesh_hosts: set[str] = set()
        self._multiproc = False
        self._pending_recovery_t0: Optional[float] = None
        self._recovery_kind = "loss"
        self._last_ckpt_pos: Optional[tuple] = None
        self._last_ckpt_t: Optional[float] = None
        self._rdzv_cache: tuple = (0.0, 0.0, None)  # (checked, mtime, doc)

    # ---- state read by the step hook (fit thread) ----
    def dead_mesh_hosts(self) -> set[str]:
        return self.supervisor.dead_hosts() & self._mesh_hosts

    def pending_grow(self) -> set[str]:
        """Joiners eligible to enter at THIS step: they hold a grow
        verdict, a checkpoint boundary has committed since the verdict
        (so the re-entry replays ~zero steps), and the ``max_hosts``
        ceiling leaves room. Cheap when nobody is joining: one dict read
        under the supervisor lock."""
        join = self.supervisor.joining_hosts()
        if not join:
            return set()
        room = self.max_hosts - len(self._mesh_hosts)
        if room <= 0:
            return set()
        ckpt_t = self._last_ckpt_t
        eligible = sorted(h for h, t in join.items()
                          if h not in self._mesh_hosts
                          and ckpt_t is not None and ckpt_t >= t)
        return set(eligible[:room])

    def pending_evict(self) -> set[str]:
        """Sustained-straggler evict verdicts eligible to fire at THIS
        step: a checkpoint boundary has committed since the verdict (so
        the unwind replays ~zero steps) and dropping them keeps the mesh
        at or above ``min_hosts``. Cheap when nobody is flagged: one
        dict read under the supervisor lock."""
        ev = self.supervisor.evict_verdicts()
        if not ev:
            return set()
        ckpt_t = self._last_ckpt_t
        if ckpt_t is None:
            return set()
        eligible = sorted(h for h, t in ev.items()
                          if h in self._mesh_hosts and ckpt_t >= t)
        room = len(self._mesh_hosts) - self.min_hosts
        return set(eligible[:max(0, room)])

    # ---- multi-process rendezvous polling (step hook, fit thread) ----
    def _read_rdzv_doc(self) -> Optional[dict]:
        """The current rendezvous doc, mtime-cached and stat-throttled:
        one os.stat per step at most, one re-read per actual change."""
        rdzv = self._rdzv
        if rdzv is None:
            return None
        checked, mtime, doc = self._rdzv_cache
        now = time.monotonic()
        if now - checked < 0.05:
            return doc
        try:
            cur = os.path.getmtime(rdzv.path)
        except OSError:
            self._rdzv_cache = (now, 0.0, None)
            return None
        if cur != mtime:
            doc = rdzv.read()
        self._rdzv_cache = (now, cur, doc)
        return doc

    def _is_leader(self) -> bool:
        """Lease-aware: the fresh leaseholder leads; an expired/absent
        lease falls back to the lowest-rank mesh host (who takes the
        lease over at propose time)."""
        return bool(self._mesh_hosts) and self._rdzv.host_id \
            == self._rdzv.elect_leader(self._mesh_hosts)

    def check_rendezvous(self, epoch: int, step: int):
        """Multi-process fleets only (single-process fits no-op): the
        deterministic membership-change machinery that rides the
        committed-step sequence. The LEADER promotes boundary-armed
        grow/evict verdicts into a rendezvous proposal whose
        ``unwind_at`` names a step a checkpoint-interval ahead; EVERY
        process (leader included) polls the doc each committed step and
        raises :class:`RendezvousPending` once it commits that step —
        identical unwind points fleet-wide, nobody stranded
        mid-collective."""
        if not self._multiproc or self._rdzv is None:
            return
        rdzv = self._rdzv
        doc = self._read_rdzv_doc()
        if (doc is None or doc["generation"] <= rdzv.generation) \
                and self._is_leader():
            # hold leadership while the fit runs: a renewed lease keeps
            # followers from taking over between membership changes
            rdzv.lease.maybe_renew()
            grow = self.pending_grow()
            evict = self.pending_evict()
            if grow or evict:
                members = sorted((self._mesh_hosts - evict) | grow)
                margin = 1
                if self.learner is not None:
                    margin = max(
                        1, self.learner.getCheckpointEverySteps() or 1)
                doc = rdzv.propose(members,
                                   unwind_at=(epoch, step + margin))
                self._rdzv_cache = (0.0, 0.0, None)
        if doc is not None and doc["generation"] > rdzv.generation:
            ua = doc.get("unwind_at")
            if ua is None or (epoch, step) >= (int(ua[0]), int(ua[1])):
                raise RendezvousPending(doc["generation"])

    def note_step(self, epoch: int, step: int):
        self.committed.append((epoch, step))
        for h in self._mesh_hosts:
            hb = self.heartbeats.get(h)
            # only beacons whose thread runs in THIS process (all of
            # them single-process; just our own on a real fleet)
            if hb is not None and hb._thread.is_alive():
                hb.beat(epoch, step)
        if self._pending_recovery_t0 is not None:
            dt = time.monotonic() - self._pending_recovery_t0
            self._pending_recovery_t0 = None
            if self._recovery_kind == "grow":
                _m_grow_recovery_seconds.observe(dt)
                self.attempts[-1]["grow_recovery_s"] = dt
                log.info("elastic grow complete: first step committed "
                         "%.2fs after the grow re-mesh began", dt)
            elif self._recovery_kind == "evict":
                _m_recovery_seconds.observe(dt)
                self.attempts[-1]["evict_recovery_s"] = dt
                log.info("elastic evict complete: first step committed "
                         "%.2fs after the straggler was dropped", dt)
            else:
                _m_recovery_seconds.observe(dt)
                self.attempts[-1]["recovery_s"] = dt
                log.info("elastic recovery complete: first step committed "
                         "%.2fs after the failure", dt)

    def note_checkpoint(self, epoch: int, step: Optional[int]):
        """A checkpoint committed durably (rename + manifest). Marks the
        grow boundary: verdicts older than this instant become
        admissible."""
        self._last_ckpt_pos = (epoch, step)
        self._last_ckpt_t = time.monotonic()

    def note_resume(self, pos, params_digest):
        self._last_ckpt_pos = pos
        self.attempts[-1]["resume_pos"] = pos
        self.attempts[-1]["resume_digest"] = params_digest
        if pos is not None and self.committed:
            # steps the previous attempt committed past the checkpoint are
            # about to be re-run — the measurable cost of the ckpt interval
            e, s = pos
            replay = sum(1 for (ce, cs) in self.committed
                         if (ce, cs) > (e, -1 if s is None else s))
            if replay:
                _m_steps_replayed.inc(replay)

    # ---- the recovery loop ----
    def _pool(self) -> list:
        self._mesh_hosts = set(self.supervisor.alive_hosts())
        return [d for h in sorted(self._mesh_hosts)
                for d in self.groups[h]]

    def fit(self, df):
        """Drive ``learner.fit``'s core through the recovery loop."""
        return self.run(lambda devices, ctx: self.learner._fit_core(
            df, devices=devices, elastic_ctx=ctx))

    def fit_stream(self, batches_fn):
        """Drive ``learner.fitStream``'s core through the recovery loop:
        a host loss re-meshes and re-enters the stream (the epoch
        restarts — a generator cannot seek — with the checkpointed
        optimizer state kept)."""
        return self.run(lambda devices, ctx: self.learner._fit_stream_core(
            batches_fn, devices=devices, elastic_ctx=ctx))

    def relaunch_host(self, host_id: str) -> HostHeartbeat:
        """Simulated-preemption RELAUNCH (single-process failure domains:
        the chaos tests): replace a killed host's
        beacon with a fresh one carrying the ``joining`` flag — exactly
        the heartbeat a real relaunched host process writes on boot. The
        supervisor turns its sustained freshness into a grow verdict."""
        if host_id not in self.groups:
            raise ValueError(f"unknown host {host_id!r}")
        old = self.heartbeats.get(host_id)
        if old is not None:
            old.kill()
        hb = HostHeartbeat(host_id, self.hb_dir, self._hb_interval,
                           joining=True)
        self.heartbeats[host_id] = hb
        hb.start()
        return hb

    def run(self, attempt_fn):
        """The recovery loop: ``attempt_fn(devices, ctx)`` until it
        returns. :class:`HostLossError` shrinks the mesh,
        :class:`HostRejoinError` grows it back,
        :class:`HostEvictError` drops a sustained straggler *before* it
        fails (all re-enter from the consensus checkpoint); transient
        failures without a verdict burn the ``max_failures`` budget on
        the same mesh."""
        from ..parallel import mesh as meshlib
        if meshlib.effective_process_count() > 1 or self._rdzv is not None:
            # real multi-process fleet. With a RendezvousCoordinator
            # armed (distributed.elastic_initialize) the fleet re-enters
            # the SAME fit through coordinator-service restart + barrier
            # re-entry; without one it keeps the fixed-fleet posture:
            # fast, clean failure instead of a hung collective, and the
            # launcher relaunches at full size against the checkpointDir
            return self._run_multiprocess(attempt_fn)
        ctx = ElasticStepContext(self)
        for h in self.heartbeats.values():
            h.start()
        self.supervisor.start()
        _register_fleet(self)
        failures = 0
        try:
            while True:
                pool = self._pool()
                self.attempts.append({"hosts": sorted(self._mesh_hosts),
                                      "devices": len(pool)})
                try:
                    with telemetry.trace.span("elastic/attempt",
                                              hosts=len(self._mesh_hosts),
                                              devices=len(pool)):
                        return attempt_fn(pool, ctx)
                except HostLossError as e:
                    self._pending_recovery_t0 = time.monotonic()
                    self._recovery_kind = "loss"
                    self._remesh(e.hosts)
                except HostRejoinError as e:
                    self._pending_recovery_t0 = time.monotonic()
                    self._recovery_kind = "grow"
                    self._grow(e.hosts)
                except HostEvictError as e:
                    self._pending_recovery_t0 = time.monotonic()
                    self._recovery_kind = "evict"
                    self._evict(e.hosts)
                except Exception as e:
                    if not default_transient(e):
                        raise
                    # transient exhaustion with no verdict yet: force a
                    # probe pass — the failure may BE the dying host
                    self._pending_recovery_t0 = time.monotonic()
                    self._recovery_kind = "loss"
                    self.supervisor.tick()
                    dead = self.dead_mesh_hosts()
                    if dead:
                        self._remesh(dead, cause=e)
                    else:
                        failures += 1
                        _m_attempt_failures.inc()
                        if failures >= self.max_failures:
                            raise ElasticFleetLost(
                                f"elastic fit failed {failures} times "
                                f"without a host verdict; last error: "
                                f"{e!r}") from e
                        log.warning(
                            "elastic fit attempt failed transiently (%r); "
                            "retrying from the latest checkpoint on the "
                            "same mesh (%d/%d)", e, failures,
                            self.max_failures)
        finally:
            _unregister_fleet(self)
            self.supervisor.stop()
            for h in self.heartbeats.values():
                h.stop()

    def _grow(self, hosts):
        """Admit grow-verdict holders back into the mesh (capped by
        ``max_hosts``) and re-enter the fit: the next attempt's pool is
        survivors + joiners and resumes from the checkpoint boundary
        that armed the grow — replays only, no fleet restart."""
        faults.inject("elastic.remesh")
        admitted = []
        for h in sorted(hosts):
            if len(self.supervisor.alive_hosts()) >= self.max_hosts:
                log.warning("host %s holds a grow verdict but the fleet "
                            "is at elasticMaxHosts (%d); leaving it "
                            "parked", h, self.max_hosts)
                break
            self.supervisor.admit(h)
            hb = self.heartbeats.get(h)
            if hb is not None:
                hb.set_joining(False)
            admitted.append(h)
        if not admitted:
            return
        _m_grows.inc()
        telemetry.trace.instant("elastic/grow",
                                joined=",".join(admitted),
                                alive=len(self.supervisor.alive_hosts()))
        telemetry.flight.note("elastic/grow", joined=admitted)
        log.warning(
            "growing the mesh: host(s) %s rejoin at checkpoint %s; "
            "%d host(s) in the pool", admitted, self._last_ckpt_pos,
            len(self.supervisor.alive_hosts()))

    def _evict(self, hosts):
        """Drop sustained-straggler hosts from the mesh at a committed
        checkpoint boundary — the loss unwind path fired *before* the
        failure. The floors are re-checked at consumption time (a death
        verdict may have landed since the evict verdict): survivors must
        satisfy ``min_hosts`` and the coordinator host (lowest alive) is
        never evicted. The evicted host stays alive and rejoins through
        the joining-heartbeat grow path once it recovers."""
        faults.inject("elastic.evict")
        victims = []
        for h in sorted(hosts):
            alive = set(self.supervisor.alive_hosts())
            if h not in alive or h not in self._mesh_hosts:
                continue
            if len(alive) - 1 < self.min_hosts:
                log.warning("host %s holds an evict verdict but dropping "
                            "it would leave %d < min_hosts (%d); leaving "
                            "it in the mesh", h, len(alive) - 1,
                            self.min_hosts)
                continue
            if h == min(alive):
                log.warning("host %s holds an evict verdict but is the "
                            "coordinator host; never evicted", h)
                continue
            self.supervisor.mark_evicted(h)
            victims.append(h)
        if not victims:
            return
        _m_remeshes.inc()
        telemetry.trace.instant("elastic/evict",
                                evicted=",".join(victims), stage="remesh",
                                alive=len(self.supervisor.alive_hosts()))
        telemetry.flight.note("elastic/evict", evicted=victims,
                              stage="remesh")
        log.warning(
            "evicting straggler host(s) %s at checkpoint %s: %d host(s) "
            "remain; resuming from the consensus checkpoint — the "
            "evicted host rejoins via the grow path once recovered",
            victims, self._last_ckpt_pos,
            len(self.supervisor.alive_hosts()))

    def _remesh(self, dead_hosts, cause=None):
        faults.inject("elastic.remesh")
        if self.supervisor.decision() == "restart":
            raise ElasticFleetLost(
                f"{len(self.supervisor.alive_hosts())} host(s) alive < "
                f"min_hosts ({self.min_hosts}); relaunch the fleet against "
                f"checkpointDir {self.learner.getCheckpointDir()!r} to "
                f"resume from the last committed step")
        _m_remeshes.inc()
        telemetry.trace.instant("elastic/remesh",
                                dead=",".join(sorted(dead_hosts)),
                                alive=len(self.supervisor.alive_hosts()))
        telemetry.flight.note("elastic/remesh", dead=sorted(dead_hosts))
        log.warning(
            "re-meshing after loss of %s: %d host(s) remain; resuming "
            "from the consensus checkpoint%s", sorted(dead_hosts),
            len(self.supervisor.alive_hosts()),
            f" (trigger: {cause!r})" if cause is not None else "")

    def _run_multiprocess(self, attempt_fn):
        import jax
        ctx = ElasticStepContext(self)
        if self._rdzv is None:
            # fixed-fleet posture (no elastic_initialize): detection +
            # fail-fast; the launcher relaunches at full size and the
            # consensus resume carries the run over
            from ..parallel import mesh as meshlib
            host_id = meshlib.stable_host_id()
            hb = self.heartbeats.get(host_id)
            self._mesh_hosts = set(self.groups)
            if hb is not None:
                hb.start()
            self.supervisor.start()
            _register_fleet(self)
            try:
                self.attempts.append({"hosts": sorted(self.groups),
                                      "devices": len(jax.devices())})
                return attempt_fn(None, ctx)
            finally:
                _unregister_fleet(self)
                self.supervisor.stop()
                if hb is not None:
                    hb.stop()
        # ---- rendezvous-armed elastic fleet ----
        self._multiproc = True
        rdzv = self._rdzv
        host_id = rdzv.host_id
        hb = rdzv.heartbeat
        if hb is not None:
            # reuse the PROCESS-LEVEL beacon elastic_initialize started:
            # it has been proving liveness since before this fit and
            # must keep doing so across re-rendezvous gaps (tighten its
            # cadence to the fit's grace if needed)
            hb.interval = min(hb.interval, self._hb_interval)
            self.heartbeats[host_id] = hb
        else:
            hb = self.heartbeats.get(host_id)
            if hb is None:
                hb = self.heartbeats[host_id] = HostHeartbeat(
                    host_id, self.hb_dir, self._hb_interval)
            hb.start()
        hb.set_generation(rdzv.generation)
        self.supervisor.start()
        _register_fleet(self)
        failures = 0
        try:
            while True:
                self._mesh_hosts = set(rdzv.ranks) or {host_id}
                self.attempts.append({"hosts": sorted(self._mesh_hosts),
                                      "devices": len(jax.devices()),
                                      "generation": rdzv.generation})
                with telemetry.trace.span("elastic/attempt",
                                          hosts=len(self._mesh_hosts),
                                          generation=rdzv.generation):
                    kind, val = self._attempt_in_thread(attempt_fn, ctx)
                if kind == "ok":
                    return val
                e = val
                if isinstance(e, RendezvousPending):
                    self._pending_recovery_t0 = time.monotonic()
                    self._recovery_kind = "grow"
                    self._rendezvous_cycle(hb)
                elif isinstance(e, (HostLossError, HostEvictError)):
                    self._pending_recovery_t0 = time.monotonic()
                    self._recovery_kind = "loss"
                    self._rendezvous_cycle(hb, dead=set(e.hosts))
                else:
                    # a failed collective (XlaRuntimeError from a gloo
                    # op on a dead peer — NOT a ConnectionError) is how
                    # a peer death usually surfaces here: force a
                    # verdict pass BEFORE deciding the error is fatal
                    self.supervisor.tick()
                    dead = self.dead_mesh_hosts()
                    doc = rdzv.read()
                    xla_err = type(e).__name__ == "XlaRuntimeError"
                    if dead or (doc is not None
                                and doc["generation"] > rdzv.generation):
                        self._pending_recovery_t0 = time.monotonic()
                        self._recovery_kind = "loss"
                        self._rendezvous_cycle(hb, dead=dead)
                    elif not default_transient(e) and not xla_err:
                        raise e
                    else:
                        failures += 1
                        _m_attempt_failures.inc()
                        if failures >= self.max_failures:
                            raise ElasticFleetLost(
                                f"elastic fit failed {failures} times "
                                f"without a host verdict; last error: "
                                f"{e!r}") from e
                        if xla_err:
                            # a failed/timed-out collective with no
                            # verdict: the gloo state is desynced (a
                            # peer re-rendezvoused or aborted) — a
                            # FRESH generation (new KV store, new
                            # contexts) is the recovery
                            log.warning(
                                "collective failed without a verdict "
                                "(%r); minting a fresh generation "
                                "(%d/%d)", e, failures,
                                self.max_failures)
                            self._pending_recovery_t0 = time.monotonic()
                            self._recovery_kind = "loss"
                            self._rendezvous_cycle(hb)
                        else:
                            log.warning(
                                "elastic fit attempt failed transiently "
                                "(%r); retrying from the latest "
                                "checkpoint (%d/%d)", e, failures,
                                self.max_failures)
        finally:
            _unregister_fleet(self)
            if self.learner is not None:
                self.learner._active_fit_thread = None
            self.supervisor.stop()
            if hb is not rdzv.heartbeat:
                hb.stop()   # the process-level beacon outlives the fit

    def _attempt_in_thread(self, attempt_fn, ctx):
        """Run one fit attempt on a WATCHED worker thread. XLA's CPU
        collectives block for up to 30 minutes when a peer dies mid-op,
        and the dispatch is synchronous — a fit thread pinned inside a
        dead collective could otherwise hold the whole fleet for that
        long. The watchdog sees the (background-thread) heartbeat
        verdict or a newer rendezvous doc, gives the attempt a short
        grace to unwind CLEANLY (check_step raising, or the collective
        surfacing its error), and otherwise FAILS FAST with
        :class:`ElasticFleetLost`: a thread pinned inside the dead
        incarnation cannot be safely abandoned in-process (it would
        unstick into — and poison — the next generation's runtime), so
        the clean recovery is a process relaunch, which re-enters the
        SAME rendezvous lineage (generation + 1) and consensus-resumes.
        In-job re-rendezvous is reserved for attempts that unwound
        cleanly — the deterministic grow/evict boundaries and surfaced
        collective errors."""
        rdzv = self._rdzv
        result: dict = {}
        done = threading.Event()

        def body():
            try:
                result["value"] = attempt_fn(None, ctx)
            except BaseException as e:   # delivered to the main loop
                result["error"] = e
            finally:
                done.set()

        t = threading.Thread(target=body, daemon=True,
                             name="elastic-attempt")
        if self.learner is not None:
            self.learner._active_fit_thread = t
        t.start()
        poll = min(0.1, max(0.02, self._hb_interval))
        while not done.wait(poll):
            dead = self.dead_mesh_hosts()
            doc = rdzv.read()
            newer = (doc is not None
                     and doc["generation"] > rdzv.generation)
            if not (dead or newer):
                continue
            # verdict landed: the attempt should unwind via check_step
            # within a step or two — unless it is pinned in C++
            if done.wait(max(1.0, 2 * self.grace)):
                break
            why = (f"dead: {sorted(dead)}" if dead
                   else f"generation {doc['generation']} pending")
            log.warning("fit attempt pinned inside a dead collective "
                        "(%s); failing fast — relaunch this process to "
                        "rejoin the rendezvous lineage", why)
            raise ElasticFleetLost(
                f"fit attempt pinned inside a dead collective ({why}); "
                f"XLA's collective timeout is ~30 minutes, so this "
                f"process fails fast instead. Relaunch it against "
                f"checkpointDir {self.checkpoint_dir!r}: it will rejoin "
                f"the rendezvous lineage (generation "
                f"{rdzv.generation} + 1) and resume from the last "
                f"committed step")
        if "error" in result:
            return "error", result["error"]
        return "ok", result.get("value")

    def _rendezvous_cycle(self, hb, dead=frozenset()):
        """One membership change on a REAL fleet: agree on the next
        generation's members, tear down the dead incarnation, restart
        the coordination service on the surviving lowest-rank host, and
        barrier back in. Retries with exponential backoff; exhaustion
        falls back to relaunch-at-full-size (ElasticFleetLost, the
        pre-rendezvous posture)."""
        from ..parallel import distributed as dist
        rdzv = self._rdzv
        host_id = rdzv.host_id
        backoff = 0.2
        last_err = None
        doc = None
        for attempt in range(self.max_failures):
            try:
                doc = rdzv.read()
                if not (doc is not None
                        and doc["generation"] > rdzv.generation
                        and host_id in doc.get("ranks", {})):
                    doc = self._negotiate_generation(hb, dead)
                rdzv.join(doc)
                break
            except (dist.RendezvousError, ConnectionError, OSError) as e:
                last_err = e
                log.warning("re-rendezvous attempt %d/%d failed (%s); "
                            "backing off %.1fs", attempt + 1,
                            self.max_failures, e, backoff)
                time.sleep(backoff)
                backoff = min(5.0, backoff * 2)
        else:
            raise ElasticFleetLost(
                f"re-rendezvous failed {self.max_failures} times (last: "
                f"{last_err!r}); relaunch the fleet at full size against "
                f"checkpointDir {self.checkpoint_dir!r} to resume from "
                f"the last committed step") from last_err
        # joined: reconcile verdict state with the new membership
        grew = len(doc["ranks"]) > len(self._mesh_hosts)
        for h in doc["ranks"]:
            if h in self.supervisor.dead_hosts():
                self.supervisor.admit(h)
        hb.set_joining(False)
        hb.set_generation(rdzv.generation)
        self._mesh_hosts = set(doc["ranks"])
        self._rdzv_cache = (0.0, 0.0, None)
        if grew:
            _m_grows.inc()
        else:
            _m_remeshes.inc()
        telemetry.trace.instant("elastic/remesh" if not grew
                                else "elastic/grow",
                                generation=rdzv.generation,
                                alive=len(self._mesh_hosts))
        log.warning("re-rendezvoused into generation %d with %d host(s) "
                    "%s", rdzv.generation, len(doc["ranks"]),
                    sorted(doc["ranks"]))

    def _negotiate_generation(self, hb, dead):
        """Decide the next generation's membership and either propose it
        (leader) or await it (everyone else). Below ``min_hosts`` the
        fleet WAITS for joining heartbeats to restore quorum — a killed
        process that relaunches re-enters the same fit instead of
        forcing a full-size relaunch."""
        from ..parallel import distributed as dist
        rdzv = self._rdzv
        host_id = rdzv.host_id
        deadline = time.monotonic() + float(os.environ.get(
            dist.ENV_REJOIN_TIMEOUT, dist.DEFAULT_REJOIN_TIMEOUT))
        while True:
            self.supervisor.tick()
            alive = set(self.supervisor.alive_hosts()) - set(dead)
            joiners = set(self.supervisor.joining_hosts())
            # a dead-verdict host whose heartbeat is FRESH and stamped
            # with the current (or newer) generation is a live member we
            # mis-verdicted across a rendezvous gap — it cannot earn a
            # grow verdict (its beacon is flagless), so recognize it
            # here or the fleet deadlocks waiting for a joiner that
            # already joined
            for h in self.supervisor.dead_hosts():
                if h in dead or h in joiners:
                    continue
                d = self.supervisor._read_doc(h)
                if (d is not None
                        and int(d.get("generation") or 0)
                        >= rdzv.generation):
                    age = self.supervisor._doc_age(
                        h, d, self.supervisor._join_fresh)
                    if age is not None and age <= self.grace:
                        joiners.add(h)
            members = sorted(alive)
            for h in sorted(joiners - alive):
                if len(members) < self.max_hosts:
                    members.append(h)
            members = sorted(members)
            if host_id not in members:
                # evicted (or mis-verdicted): park as a joiner until a
                # future generation readmits us
                hb.set_joining(True)
                return rdzv.await_membership(rdzv.generation + 1)
            if len(members) >= self.min_hosts:
                # lease-aware election: the fresh leaseholder proposes;
                # an expired lease is taken over by the lowest-rank
                # fresh member (members only contains fresh hosts)
                if host_id == rdzv.elect_leader(members, max_age=0.0):
                    return rdzv.propose(members)
                # follower: wait as long as the leader might (it may be
                # holding for quorum before proposing)
                return rdzv.await_membership(
                    rdzv.generation + 1,
                    timeout=max(5.0, deadline - time.monotonic()))
            if time.monotonic() >= deadline:
                raise ElasticFleetLost(
                    f"{len(members)} host(s) alive < min_hosts "
                    f"({self.min_hosts}) and no rejoin within the "
                    f"window; relaunch the fleet against checkpointDir "
                    f"{self.checkpoint_dir!r} to resume")
            log.warning("fleet below min_hosts (%d alive, need %d); "
                        "waiting for joining heartbeats",
                        len(members), self.min_hosts)
            time.sleep(max(0.1, self.supervisor.probe_interval))
