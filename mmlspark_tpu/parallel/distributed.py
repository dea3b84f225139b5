"""Multi-host distributed backend: JAX coordination-service rendezvous.

Replaces both multi-node rendezvous mechanisms in the reference (SURVEY.md
§2.7): the LightGBM machine-list/port handshake assembled from Spark executor
discovery (reference: lightgbm/.../LightGBMUtils.scala:98-160 feeding
``LGBM_NetworkInit``, TrainUtils.scala:141-142) and the MPI hostfile written
for ssh'd ``mpirun`` (cntk-train/.../CommandBuilders.scala:135-147,241-243).

Here every host process calls ``initialize(...)`` (or ``initialize_from_env``
under a launcher that exports the coordinator address); JAX's coordination
service does the rendezvous over DCN, after which ``jax.devices()`` spans the
whole pod/slice and a single global ``Mesh`` drives ICI/DCN collectives — no
ssh, no hostfiles, no socket rings.

Single-process (local[*]-style) use needs no initialize call at all — the
same code paths run on the local devices, the analog of the reference's
partitions-as-workers local mode (SURVEY.md §4).

Failure model: a worker missing at rendezvous fails the fleet inside
MMLTPU_INIT_TIMEOUT (default 120 s, LightGBM's bound); a worker dying
BETWEEN collectives is caught by coordination-service heartbeats
(MMLTPU_HEARTBEAT_TIMEOUT) — the survivors terminate with an error inside
the bound instead of hanging in the next collective. Recovery = relaunch
the fleet and refit with the same checkpointDir: TpuLearner resumes from
the last complete epoch (the crash→relaunch→resume path has a real
two-process test in tests/test_parallel_depth.py).
"""

from __future__ import annotations

import json
import os
import socket
import threading
import time
from typing import Optional, Sequence

import jax

from .. import telemetry
from ..core.utils import get_logger
from . import mesh as meshlib

log = get_logger("distributed")

_m_generation = telemetry.registry.gauge(
    "mmlspark_rendezvous_generation",
    "the jax.distributed incarnation this process is currently joined "
    "to (bumped by every elastic re-rendezvous; 0 = never rendezvoused)")
_m_rendezvous = telemetry.registry.counter(
    "mmlspark_rendezvous_total",
    "re-rendezvous joins completed (coordinator-service restart + "
    "barrier re-entry into a new generation)")
_m_lease_term = telemetry.registry.gauge(
    "mmlspark_lease_term",
    "the leader-lease term this process last observed (bumped by every "
    "takeover; 0 = no lease yet)")
_m_lease_renewals = telemetry.registry.counter(
    "mmlspark_lease_renewals",
    "leader-lease renewals written by this process as the holder")
_m_lease_takeovers = telemetry.registry.counter(
    "mmlspark_lease_takeovers",
    "leader-lease acquisitions (fresh grants and expired-lease "
    "takeovers by the lowest-rank fresh host)")

# launcher-agnostic env contract (set by the Spark-executor / TPU-VM launcher)
ENV_COORDINATOR = "MMLTPU_COORDINATOR"       # "host:port" of process 0
ENV_NUM_PROCESSES = "MMLTPU_NUM_PROCESSES"
ENV_PROCESS_ID = "MMLTPU_PROCESS_ID"
ENV_INIT_TIMEOUT = "MMLTPU_INIT_TIMEOUT"     # seconds to wait at rendezvous
ENV_HEARTBEAT_TIMEOUT = "MMLTPU_HEARTBEAT_TIMEOUT"  # dead-worker detection

# the reference's LightGBM rendezvous blocks at most 120 s
# (LightGBMConstants.scala:9-12 defaultListenTimeout); same bound here so a
# missing worker fails the job instead of hanging the fleet
DEFAULT_INIT_TIMEOUT = 120

_initialized = False


def _enable_cpu_collectives() -> None:
    """Multi-process CPU fleets need a real cross-process collective
    implementation: the plain CPU client raises "Multiprocess computations
    aren't implemented on the CPU backend" at the first allgather. jaxlib
    ships gloo TCP collectives behind a config knob — select them whenever
    the job will run on the CPU platform (the multiproc-CPU smoke, local
    fleet rehearsal, CI). Must run BEFORE the backend client is created;
    initialize() is the single choke point every launcher goes through.
    On TPU/GPU jobs the knob is irrelevant and skipped."""
    # platform must be decided WITHOUT touching jax.devices(): instantiating
    # the backend here would bake the collectives choice in before the knob
    # lands. The config value covers jax.config.update("jax_platforms",...)
    # callers (tests, the smoke workers); the env vars cover launchers.
    platforms = (getattr(jax.config, "jax_platforms", None)
                 or os.environ.get("JAX_PLATFORMS", "")
                 or os.environ.get("JAX_PLATFORM_NAME", "")).lower()
    if platforms != "cpu":
        return
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def is_initialized() -> bool:
    return _initialized


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               local_device_ids: Optional[Sequence[int]] = None,
               init_timeout: Optional[int] = None,
               heartbeat_timeout: Optional[int] = None) -> None:
    """Join the global JAX runtime. Process 0's address is the rendezvous
    point (the machine-list/hostfile role); blocks until all processes check
    in, like LGBM_NetworkInit's 120s barrier — but heartbeated and reusable
    across every collective rather than per-training-job. A worker that
    never shows up fails the rendezvous after ``init_timeout`` (default
    120 s, the reference's bound); a worker that dies later is detected by
    missed heartbeats and takes the job down rather than hanging it."""
    global _initialized
    if _initialized:
        log.info("distributed runtime already initialized; skipping")
        return
    if init_timeout is None:
        init_timeout = int(os.environ.get(ENV_INIT_TIMEOUT,
                                          DEFAULT_INIT_TIMEOUT))
    kwargs = {}
    if heartbeat_timeout is None and ENV_HEARTBEAT_TIMEOUT in os.environ:
        heartbeat_timeout = int(os.environ[ENV_HEARTBEAT_TIMEOUT])
    if heartbeat_timeout is not None:
        kwargs["heartbeat_timeout_seconds"] = heartbeat_timeout
    _enable_cpu_collectives()
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id,
                               local_device_ids=local_device_ids,
                               initialization_timeout=init_timeout,
                               **kwargs)
    _initialized = True
    log.info("distributed init: process %d/%d, %d local / %d global devices",
             jax.process_index(), jax.process_count(),
             jax.local_device_count(), jax.device_count())


def initialize_from_env() -> bool:
    """Initialize from the MMLTPU_* env contract when present (the launcher
    writes it per executor the way the reference's driver writes
    hostfile.txt). Returns True when distributed init ran; False means
    single-process mode — both are valid, same downstream code."""
    addr = os.environ.get(ENV_COORDINATOR)
    if not addr:
        return False
    initialize(coordinator_address=addr,
               num_processes=int(os.environ[ENV_NUM_PROCESSES]),
               process_id=int(os.environ[ENV_PROCESS_ID]))
    return True


#: where the persistent compile cache lives when the environment does not
#: place it: one fixed directory beside the package (the path is part of
#: the cache key, so it must never depend on cwd, pid or time)
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> None:
    """Place JAX's persistent compilation cache. Runs once, from the
    package's ``__init__``, so fit, transform and serving entry points all
    share it. ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and
    this sets no directory. Unset: :data:`DEFAULT_COMPILE_CACHE_DIR`.
    Fleet workers, trial subprocesses and sealed one-shot machines
    recompile the same programs on every launch; the cache turns that
    into a disk read."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)


def shutdown() -> None:
    global _initialized
    if _initialized:
        jax.distributed.shutdown()
        _initialized = False


# ---- elastic re-rendezvous -------------------------------------------------
#
# The fail-fast model above is right for fixed fleets: a dead peer takes
# the job down inside the heartbeat bound and the launcher relaunches at
# full size. Elastic fleets want the JAMPI barrier-re-entry shape instead
# (PAPERS.md arxiv 2007.01811): the survivors tear the coordination
# service down, restart it on the surviving lowest-rank host, and every
# member re-enters the rendezvous barrier under a NEW generation — so a
# kill -9'd process can relaunch and join the *same running fit*, and a
# straggler can be evicted without losing the fleet.
#
# The generation is carried by an atomically-renamed ``rendezvous.json``
# on the job's shared checkpoint storage (the same trust anchor the
# consensus checkpoints use): {generation, address, ranks}. Only the
# leader (lowest-rank surviving host) writes it; everyone else polls.
# A process may only ever JOIN a generation strictly newer than the one
# it last held AND that names it in ``ranks`` — a stale-generation
# process can therefore never join the wrong incarnation; it parks in
# the joining-heartbeat path until a future generation includes it.
#
# Teardown deliberately does NOT call client.shutdown(): with a dead
# peer the coordination-service shutdown barrier aborts the process
# (client.h LogFatal). Instead the dead generation's client/service are
# LEAKED (bounded by the number of re-rendezvous events), the cached XLA
# backends are dropped, and the new generation's client is built with a
# benign missed-heartbeat callback + shutdown_on_destruction=False so
# neither the leak nor a later peer death can terminate the process —
# the elastic runtime's own heartbeat verdicts are the failure signal.

RENDEZVOUS_DOC = "rendezvous.json"

ENV_HOST_ADDRESS = "MMLTPU_HOST_ADDRESS"     # advertised rendezvous addr
ENV_REJOIN_TIMEOUT = "MMLTPU_REJOIN_TIMEOUT"  # seconds to wait for a
DEFAULT_REJOIN_TIMEOUT = 120.0                # generation that names us

_leaked_incarnations: list = []   # dead generations' client/service pairs
_rdzv_coordinator: Optional["RendezvousCoordinator"] = None


class RendezvousError(RuntimeError):
    """A re-rendezvous attempt failed (proposal raced, barrier timed
    out, init refused). Retried with backoff by the caller; exhaustion
    falls back to relaunch-at-full-size (ElasticFleetLost)."""


def rendezvous_coordinator() -> Optional["RendezvousCoordinator"]:
    """The process-wide rendezvous coordinator, armed by
    :func:`elastic_initialize` (None = fixed-fleet mode: a member loss
    fails fast and the launcher relaunches)."""
    return _rdzv_coordinator


LEASE_DOC = "lease.json"
ENV_LEASE_TIMEOUT = "MMLTPU_LEASE_TIMEOUT"
DEFAULT_LEASE_TIMEOUT = 5.0


class LeaderLease:
    """A renewable leader lease over one shared-storage file.

    PR 10's rendezvous made the *generation* race-free but left the
    *proposer election* racy: "lowest-rank survivor proposes" is a rule
    each host evaluates from its own heartbeat view, and two hosts with
    briefly divergent views could both propose — bounded only by
    last-write-wins on the doc rename. The lease serializes proposals
    the way production control planes do:

    * ``lease.json`` carries ``{holder, term, seq, time}``. The holder
      renews it (``seq`` + 1, same ``term``) while it leads; every
      renewal is an atomic rename, so readers never see a torn doc.
    * Freshness is judged like PR 10's heartbeats: a reader tracks when
      the ``(term, seq)`` pair last *advanced on its own monotonic
      clock* — a skewed writer wall clock can neither fake freshness
      nor fake expiry. A lease that has not advanced for
      ``timeout`` seconds (``MMLTPU_LEASE_TIMEOUT``, default 5) is
      **expired**.
    * An expired (or absent) lease is taken over with ``term + 1`` by
      the lowest-rank fresh host (:meth:`RendezvousCoordinator.propose`
      enforces *who*); the takeover re-reads the file after its rename,
      so two racing takeovers resolve deterministically — exactly one
      proceeds, the loser raises and re-enters election as a follower.
    * A **stale leader can never publish**: its term is behind the
      file's, so :meth:`renew` refuses, ``propose`` re-validates the
      lease after the doc rename (a void proposal raises instead of
      standing), and followers refuse docs stamped with an old
      ``lease_term`` — the late-proposal race PR 10 bounded with
      retries is now refused by generation.
    """

    def __init__(self, directory: str, host_id: str,
                 timeout: Optional[float] = None):
        self.directory = directory
        self.host_id = host_id
        if timeout is None:
            timeout = float(os.environ.get(ENV_LEASE_TIMEOUT,
                                           DEFAULT_LEASE_TIMEOUT))
        self.timeout = float(timeout)
        #: the term THIS process last acquired (0 = never held). A
        #: relaunched process starts at 0 and must re-acquire — its old
        #: incarnation's file term is someone it can no longer speak for.
        self.term = 0
        self._seen: tuple[int, int] = (0, 0)   # last observed (term, seq)
        self._seen_at = time.monotonic()       # reader clock at last advance
        self._last_renewal = 0.0
        self._cache: tuple[float, Optional[dict]] = (0.0, None)

    @property
    def path(self) -> str:
        return os.path.join(self.directory, LEASE_DOC)

    def read(self) -> Optional[dict]:
        try:
            with open(self.path, "r", encoding="utf-8") as f:
                doc = json.load(f)
            if not isinstance(doc.get("term"), int):
                return None
            return doc
        except (OSError, ValueError):
            return None

    def observe(self, max_age: float = 0.0) -> Optional[dict]:
        """Read the lease and advance the reader-side freshness clock
        whenever ``(term, seq)`` moved. Chaos site ``distributed.lease``
        covers every lease-file round-trip. ``max_age`` > 0 reuses the
        last read within that window (per-committed-step election must
        not turn into a per-step shared-FS read)."""
        if max_age > 0:
            at, doc = self._cache
            if time.monotonic() - at < max_age:
                return doc
        from ..resilience import faults
        faults.inject("distributed.lease")
        doc = self.read()
        self._cache = (time.monotonic(), doc)
        if doc is not None:
            key = (int(doc.get("term", 0)), int(doc.get("seq", 0)))
            if key != self._seen:
                self._seen = key
                self._seen_at = time.monotonic()
            _m_lease_term.set(key[0])
        return doc

    def expired(self, max_age: float = 0.0) -> bool:
        """True when the lease is absent, or its ``(term, seq)`` has not
        advanced for ``timeout`` seconds of THIS reader's monotonic
        clock. A reader that just started watching a stale file still
        waits out one full window — lease semantics require observing
        the silence, not just old metadata."""
        if self.observe(max_age=max_age) is None:
            return True
        return time.monotonic() - self._seen_at >= self.timeout

    def held(self) -> bool:
        """True while the file names this process as holder at the term
        it acquired (a relaunched process, term 0, never holds)."""
        doc = self.read()
        return (self.term > 0 and doc is not None
                and doc.get("holder") == self.host_id
                and int(doc.get("term", 0)) == self.term)

    def _write(self, term: int, seq: int):
        os.makedirs(self.directory, exist_ok=True)
        doc = {"holder": self.host_id, "term": term, "seq": seq,
               "time": time.time()}
        # unique tmp per process: racing takeovers must not clobber each
        # other's tmp files. No fsync before the rename ON PURPOSE (the
        # heartbeat posture): a lease needs READ atomicity, not crash
        # durability — a leader that crashes SHOULD lose its lease, and
        # an fsync per renewal would hammer the shared filesystem.
        tmp = f"{self.path}.tmp.{os.getpid()}.{threading.get_ident()}"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(doc, f)
        # graftlint: disable=protocol-rename-before-fsync
        os.replace(tmp, self.path)
        self._seen = (term, seq)
        self._seen_at = time.monotonic()
        self._cache = (self._seen_at, doc)

    def renew(self):
        """Holder-side keep-alive: bump ``seq`` at the held term. Raises
        :class:`RendezvousError` when the lease moved on (takeover) —
        the caller has been deposed and must re-enter election."""
        from ..resilience import faults
        faults.inject("distributed.lease")
        doc = self.read()
        if (doc is None or doc.get("holder") != self.host_id
                or int(doc.get("term", 0)) != self.term or self.term == 0):
            raise RendezvousError(
                f"{self.host_id} lost the leader lease (now held by "
                f"{(doc or {}).get('holder')!r} at term "
                f"{(doc or {}).get('term')})")
        self._write(self.term, int(doc.get("seq", 0)) + 1)
        self._last_renewal = time.monotonic()
        _m_lease_renewals.inc()

    def maybe_renew(self):
        """Opportunistic holder keep-alive, throttled to a third of the
        timeout (callers can invoke it per committed step for free)."""
        if self.term == 0:
            return
        if time.monotonic() - self._last_renewal < self.timeout / 3.0:
            return
        try:
            self.renew()
        except RendezvousError:
            self.term = 0      # deposed: stop renewing a lost lease

    def acquire(self) -> dict:
        """Take (over) the lease at ``term + 1``. Refused while another
        holder is fresh; a write race is resolved by the post-rename
        re-read — exactly one contender's doc stands."""
        from ..resilience import faults
        faults.inject("distributed.lease")
        doc = self.observe()
        if (doc is not None and doc.get("holder") != self.host_id
                and not self.expired()):
            raise RendezvousError(
                f"leader lease is held fresh by {doc['holder']!r} (term "
                f"{doc['term']}); {self.host_id} must not take over")
        new_term = (int(doc.get("term", 0)) if doc else 0) + 1
        self._write(new_term, 1)
        cur = self.read()
        if (cur is None or cur.get("holder") != self.host_id
                or int(cur.get("term", 0)) != new_term):
            raise RendezvousError(
                f"lease takeover raced: {self.host_id} wrote term "
                f"{new_term} but the file now holds "
                f"{(cur or {}).get('holder')!r} at term "
                f"{(cur or {}).get('term')}")
        self.term = new_term
        self._last_renewal = time.monotonic()
        _m_lease_takeovers.inc()
        _m_lease_term.set(new_term)
        telemetry.trace.instant("lease/takeover", holder=self.host_id,
                                term=new_term)
        telemetry.flight.note("lease/takeover", holder=self.host_id,
                              term=new_term)
        log.warning("leader lease acquired by %s at term %d",
                    self.host_id, new_term)
        return cur


def _advertised_address() -> str:
    """The address peers can reach THIS host on (the new coordinator
    service binds here after a leader takeover)."""
    addr = os.environ.get(ENV_HOST_ADDRESS)
    if addr:
        return addr
    try:
        return socket.gethostbyname(socket.gethostname())
    except OSError:
        return "127.0.0.1"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _init_elastic_client(address: str, num_processes: int, process_id: int,
                         init_timeout: int):
    """Stand up one generation's coordination service (leader) + client,
    with the survivable failure posture: the ELASTIC runtime's own file
    heartbeats are the failure detector, so the coordination service's
    redundant one is configured effectively inert (a peer death must
    never let the service flag an error back into surviving clients —
    the default client reaction to a polled error is process
    termination), and the client is built with shutdown_on_destruction
    off plus a log-only missed-heartbeat callback as the last line of
    defense."""
    from jax._src import distributed as dist_internal
    from jax._src.lib import xla_extension as xe
    st = dist_internal.global_state
    if st.client is not None:
        raise RendezvousError("previous incarnation still attached; "
                              "teardown_for_rendezvous() first")
    # ~11 days of missed heartbeats before the redundant detector acts
    hb_interval, hb_tolerance = 10, 100_000
    if process_id == 0:
        port = address.rsplit(":", 1)[1]
        st.service = xe.get_distributed_runtime_service(
            f"[::]:{port}", num_processes,
            heartbeat_interval=hb_interval,
            max_missing_heartbeats=hb_tolerance)

    def _on_peer_trouble(*status):
        log.warning("coordination-service error (peer died or network "
                    "trouble); elastic heartbeat verdicts drive the "
                    "recovery: %s", status)

    st.client = xe.get_distributed_runtime_client(
        address, process_id, init_timeout=init_timeout,
        shutdown_timeout=10,
        heartbeat_interval=hb_interval,
        max_missing_heartbeats=hb_tolerance,
        missed_heartbeat_callback=_on_peer_trouble,
        shutdown_on_destruction=False, use_compression=True)
    st.client.connect()
    st.process_id = process_id
    st.num_processes = num_processes
    st.coordinator_address = address
    _register_exit_detach()
    global _initialized
    _initialized = True


_exit_detach_registered = False


def _register_exit_detach():
    """jax registers an atexit ``clean_up`` that runs the coordination
    shutdown BARRIER — against an elastic fleet whose members exit at
    different times (a peer may be long dead) that barrier hangs or
    aborts. Our handler registers LATER, so it runs FIRST (atexit is
    LIFO): when the fleet looks healthy (every current-generation
    peer's heartbeat file is fresh — everyone is exiting through the
    same barrier), shut down gracefully inside the 10 s bound; when a
    peer is dead, DETACH instead — an abrupt disconnect must never let
    the coordination service flag an error back into this (or another)
    exiting process, because the error-poll callback crossing into
    Python during interpreter teardown aborts the process."""
    global _exit_detach_registered
    if _exit_detach_registered:
        return
    _exit_detach_registered = True
    import atexit
    from jax._src import distributed as dist_internal

    def _detach():
        st = dist_internal.global_state
        client, service = st.client, st.service
        st.client = None
        st.service = None
        st.preemption_sync_manager = None
        if client is None:
            return
        rdzv = _rdzv_coordinator
        healthy = True
        if rdzv is not None and rdzv.ranks:
            now = time.time()
            for h in rdzv.ranks:
                if h == rdzv.host_id:
                    continue
                try:
                    fresh = now - os.path.getmtime(os.path.join(
                        rdzv.directory, f"hb_{h}.json")) <= 10.0
                except OSError:
                    fresh = False
                if not fresh:
                    healthy = False
                    break
        if healthy:
            try:
                client.shutdown()
                if service is not None:
                    service.shutdown()
                return
            except Exception:
                pass
        _leaked_incarnations.append((client, service))

    atexit.register(_detach)


def teardown_for_rendezvous() -> None:
    """Detach from the current (dead) incarnation WITHOUT the shutdown
    barrier, and drop the cached XLA backends so the next collective
    program instantiates against the new generation's KV store. The old
    client/service objects are leaked on purpose — destroying them runs
    the fatal shutdown path."""
    from jax._src import distributed as dist_internal
    from jax._src import xla_bridge
    st = dist_internal.global_state
    _leaked_incarnations.append((st.client, st.service))
    st.client = None
    st.service = None
    st.preemption_sync_manager = None
    st.coordinator_address = None
    st.process_id = 0
    st.num_processes = 1
    xla_bridge._clear_backends()
    jax.clear_caches()
    global _initialized
    _initialized = False


class RendezvousCoordinator:
    """Generation-stamped membership + barrier re-entry for one elastic
    job (one instance per process; ``host_id`` is the process's STABLE
    identity — its launch rank — which survives re-ranking across
    generations)."""

    def __init__(self, directory: str, host_id: str,
                 init_timeout: Optional[int] = None,
                 lease_timeout: Optional[float] = None):
        self.directory = directory
        self.host_id = host_id
        self.generation = 0
        self.ranks: dict[str, int] = {}
        #: proposals are serialized by a leader lease — see LeaderLease
        self.lease = LeaderLease(directory, host_id,
                                 timeout=lease_timeout)
        #: the PROCESS-LEVEL heartbeat beacon (started by
        #: elastic_initialize, reused by the fit coordinator): the host
        #: must never go silent between joining a generation and the fit
        #: loop taking over, or peers re-issue a death verdict into the
        #: gap
        self.heartbeat = None
        self.init_timeout = (init_timeout if init_timeout is not None
                             else int(os.environ.get(
                                 ENV_INIT_TIMEOUT, DEFAULT_INIT_TIMEOUT)))

    @property
    def path(self) -> str:
        return os.path.join(self.directory, RENDEZVOUS_DOC)

    def read(self) -> Optional[dict]:
        try:
            with open(self.path, "r", encoding="utf-8") as f:
                doc = json.load(f)
            if not isinstance(doc.get("generation"), int):
                return None
            return doc
        except (OSError, ValueError):
            return None

    def elect_leader(self, members, max_age: float = 0.05) -> str:
        """Lease-aware leader election over ``members``: the fresh lease
        holder when it is a member, else the lowest-rank member (who
        will take over the expired/absent lease at propose time)."""
        members = sorted(members)
        doc = self.lease.observe(max_age=max_age)
        if doc is not None and not self.lease.expired(max_age=max_age):
            holder = doc.get("holder")
            if holder in members:
                return holder
        return members[0] if members else self.host_id

    def propose(self, hosts, unwind_at: Optional[tuple] = None) -> dict:
        """Leader-side: mint the next generation over ``hosts`` (ranks
        assigned in sorted host order, so the lowest surviving host is
        rank 0 and carries the restarted coordinator service) and commit
        the doc atomically. ``unwind_at`` tells still-stepping members
        the (epoch, step) after which they must unwind and join —
        the deterministic grow/evict boundary.

        Proposals are serialized by the leader lease: the fresh holder
        renews and proposes; an absent/expired lease is taken over by
        the lowest-rank host of the proposal set; anyone else is
        refused. After the doc rename the lease is re-validated — a
        leader deposed mid-proposal raises instead of publishing, and a
        fresh leader whose doc was overwritten by a stale straggler
        rewrites it (the straggler cannot renew, so this converges)."""
        from ..resilience import faults
        faults.inject("distributed.rendezvous")
        hosts = sorted(set(hosts))
        if self.lease.held():
            self.lease.renew()
        else:
            lease_doc = self.lease.observe()
            if (lease_doc is not None
                    and lease_doc.get("holder") != self.host_id
                    and not self.lease.expired()):
                raise RendezvousError(
                    f"{self.host_id} proposed a generation but "
                    f"{lease_doc['holder']!r} holds a fresh leader lease "
                    f"(term {lease_doc['term']})")
            if self.host_id != hosts[0]:
                raise RendezvousError(
                    f"{self.host_id} proposed a generation but {hosts[0]} "
                    f"is the surviving leader (lowest-rank fresh host "
                    f"takes the expired lease)")
            self.lease.acquire()
        cur = self.read()
        gen = max(self.generation,
                  cur["generation"] if cur else 0) + 1
        doc = {"generation": gen,
               "address": f"{_advertised_address()}:{_free_port()}",
               "ranks": {h: i for i, h in enumerate(hosts)},
               "num_processes": len(hosts),
               "lease_term": self.lease.term,
               "time": time.time()}
        if unwind_at is not None:
            doc["unwind_at"] = list(unwind_at)
        os.makedirs(self.directory, exist_ok=True)
        for _attempt in range(8):
            # same commit discipline as checkpoints (fsync BEFORE the
            # atomic rename — lint-enforced by
            # protocol-rename-before-fsync): a torn rendezvous doc would
            # strand relaunched processes on a generation that never
            # existed
            tmp = f"{self.path}.tmp.{os.getpid()}"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(doc, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.path)
            if not self.lease.held():
                raise RendezvousError(
                    f"{self.host_id} lost the leader lease during the "
                    f"proposal; generation {gen} is void (refused by "
                    f"generation at every follower)")
            stood = self.read()
            if (stood is not None
                    and stood.get("generation") == gen
                    and stood.get("address") == doc["address"]
                    and stood.get("lease_term") == self.lease.term):
                break
            log.warning("rendezvous doc overwritten by a stale proposal; "
                        "leaseholder %s rewrites generation %d",
                        self.host_id, gen)
        else:
            raise RendezvousError(
                f"rendezvous doc for generation {gen} would not stand "
                f"after 8 rewrites")
        log.warning("rendezvous generation %d proposed: %d host(s) %s at "
                    "%s (lease term %d)", gen, len(hosts), hosts,
                    doc["address"], self.lease.term)
        return doc

    def await_membership(self, min_generation: int,
                         timeout: Optional[float] = None) -> dict:
        """Follower-side: poll the doc until a generation >=
        ``min_generation`` names this host. A doc that omits us (we were
        evicted, or the leader hasn't seen our joining heartbeat yet)
        keeps us parked — the stale-generation guard."""
        from ..resilience import faults
        faults.inject("distributed.rendezvous")
        if timeout is None:
            timeout = float(os.environ.get(ENV_REJOIN_TIMEOUT,
                                           DEFAULT_REJOIN_TIMEOUT))
        deadline = time.monotonic() + timeout
        while True:
            doc = self.read()
            if doc is not None and "lease_term" in doc:
                # a stale leader's LATE proposal: stamped with a lease
                # term the fleet has moved past — refused by generation
                # (the fresh leaseholder rewrites the doc; keep polling)
                lease_doc = self.lease.read()
                if (lease_doc is not None
                        and int(doc["lease_term"])
                        < int(lease_doc.get("term", 0))):
                    doc = None
            if (doc and doc["generation"] >= min_generation
                    and self.host_id in doc.get("ranks", {})):
                return doc
            if time.monotonic() >= deadline:
                raise RendezvousError(
                    f"no rendezvous generation >= {min_generation} named "
                    f"{self.host_id} within {timeout:.0f}s")
            time.sleep(0.05)

    def join(self, doc: dict) -> None:
        """Tear down the old incarnation and enter ``doc``'s: restart /
        connect the coordination service, then barrier re-entry so every
        member is known present before the fit re-enters. Refuses a doc
        whose generation is not strictly newer than the one this process
        last held."""
        gen = int(doc["generation"])
        if gen <= self.generation:
            raise RendezvousError(
                f"stale generation {gen} (this process already held "
                f"{self.generation}) — refusing to join an old "
                f"incarnation")
        rank = doc["ranks"].get(self.host_id)
        if rank is None:
            raise RendezvousError(
                f"generation {gen} does not include {self.host_id}")
        with telemetry.trace.span("distributed/rendezvous",
                                  generation=gen, rank=rank,
                                  hosts=len(doc["ranks"])):
            # previous incarnation attached? detach WITHOUT touching
            # jax.devices()/process_count() — those would instantiate a
            # backend before the new generation's client exists
            from jax._src import distributed as dist_internal
            if dist_internal.global_state.client is not None:
                teardown_for_rendezvous()
            _enable_cpu_collectives()
            _init_elastic_client(doc["address"], int(doc["num_processes"]),
                                 int(rank), self.init_timeout)
            # barrier re-entry: every member of the new generation checks
            # in before anyone dispatches a collective
            dist_internal.global_state.client.wait_at_barrier(
                f"mmlspark-rdzv-{gen}", int(self.init_timeout * 1000))
        self.generation = gen
        self.ranks = dict(doc["ranks"])
        _m_generation.set(gen)
        _m_rendezvous.inc()
        telemetry.flight.note("distributed/rendezvous", generation=gen,
                              rank=rank, hosts=len(doc["ranks"]))
        log.warning("joined rendezvous generation %d as rank %d/%d "
                    "(%d local / %d global devices)", gen, rank,
                    int(doc["num_processes"]), jax.local_device_count(),
                    jax.device_count())


def _incarnation_live(directory: str, doc: dict, self_host: str,
                      window: float = 10.0) -> bool:
    """Is the doc's incarnation still running? True when any OTHER
    member's heartbeat file was modified within ``window`` seconds
    (reader-side FS mtime — no writer wall-clock trust). A ``joining``
    heartbeat does NOT count: it is a parked waiter, not a running
    member — two relaunched processes must not each mistake the other
    for a live fit and park forever."""
    now = time.time()
    for host in doc.get("ranks", {}):
        if host == self_host:
            continue
        path = os.path.join(directory, f"hb_{host}.json")
        try:
            mtime = os.path.getmtime(path)
            with open(path, "r", encoding="utf-8") as f:
                member_doc = json.load(f)
        except (OSError, ValueError):
            continue
        if now - mtime <= window and not member_doc.get("joining"):
            return True
    return False


def elastic_initialize(checkpoint_dir: str,
                       host_id: Optional[str] = None,
                       rejoin_timeout: Optional[float] = None) -> bool:
    """Elastic-fleet entry point: join (or REJOIN) the job's current
    incarnation through the shared-storage rendezvous protocol instead
    of the fixed-fleet env contract. Every launch and relaunch calls
    this; the three cases resolve themselves:

    * **fresh job** (no rendezvous doc): the env-contract leader
      (process 0) proposes generation 1 over the launch fleet; everyone
      joins it. Falls back to single-process mode (returns False) when
      the env contract is absent.
    * **rejoin** (doc present, incarnation live, we're not in it): this
      is a relaunched/evicted host. Write a ``joining`` heartbeat and
      park until the running fit's leader admits us into a future
      generation at a checkpoint boundary, then join it.
    * **full relaunch** (doc present, incarnation dead): the launcher
      restarted the whole fleet; process 0 proposes generation N+1 over
      the launch fleet and consensus-resume carries the run over.

    Returns True when a distributed incarnation was joined."""
    global _rdzv_coordinator
    addr = os.environ.get(ENV_COORDINATOR)
    n_env = int(os.environ.get(ENV_NUM_PROCESSES, "0") or 0)
    pid_env = int(os.environ.get(ENV_PROCESS_ID, "0") or 0)
    if host_id is None:
        host_id = meshlib.stable_host_id()
    from ..resilience.elastic import heartbeat_dir
    hb_dir = heartbeat_dir(checkpoint_dir)
    os.makedirs(hb_dir, exist_ok=True)
    rdzv = RendezvousCoordinator(hb_dir, host_id)
    from ..resilience.elastic import (HostHeartbeat, _hb_interval_default,
                                      _grace_default)
    hb = HostHeartbeat(host_id, hb_dir,
                       _hb_interval_default(_grace_default()))
    doc = rdzv.read()
    launch_hosts = [f"host{i}" for i in range(n_env)]
    if doc is None:
        if not addr or n_env <= 1:
            return False                    # single-process mode
        if pid_env == 0:
            doc = rdzv.propose(launch_hosts)
        else:
            doc = rdzv.await_membership(1, timeout=rejoin_timeout)
        hb.start()
        rdzv.join(doc)
    elif _incarnation_live(hb_dir, doc, host_id):
        # REJOIN a running fit: park behind a joining heartbeat until a
        # generation names us (the grow path's checkpoint boundary).
        # Even when the live doc still names this host (killed and
        # relaunched before the leader noticed), the OLD incarnation's
        # connections are gone — only a fresh generation is joinable;
        # the joining flag self-reports the restart so the leader's
        # death pass drops the old membership promptly.
        hb.set_joining(True)
        hb.start()
        log.warning("rendezvous doc generation %d is live; %s parks "
                    "with a joining heartbeat until readmitted",
                    doc["generation"], host_id)
        target = rdzv.await_membership(doc["generation"] + 1,
                                       timeout=rejoin_timeout)
        rdzv.join(target)
        hb.set_joining(False)
    else:
        # dead incarnation: full-fleet relaunch over the env contract
        if not addr or n_env <= 1:
            return False
        if pid_env == 0:
            doc = rdzv.propose(launch_hosts)
        else:
            doc = rdzv.await_membership(doc["generation"] + 1,
                                        timeout=rejoin_timeout)
        hb.start()
        rdzv.join(doc)
    # the beacon OUTLIVES this call (the fit coordinator reuses it):
    # between generations and fits the host must keep proving liveness
    hb.set_generation(rdzv.generation)
    rdzv.heartbeat = hb
    _rdzv_coordinator = rdzv
    return True


def global_mesh(axes: Optional[dict[str, int]] = None) -> "jax.sharding.Mesh":
    """A mesh over ALL processes' devices. Default: 1-D ``data`` axis over
    every chip in the job (pure DP, the reference's only strategy); pass
    ``axes`` for dp x tp x sp x ep layouts. Put ``data`` outermost so DP
    gradient all-reduce crosses DCN once per step while tp/sp/ep ride ICI."""
    if axes is None:
        axes = {"data": jax.device_count()}
    return meshlib.make_mesh(axes, devices=jax.devices())


def process_barrier(name: str = "barrier") -> None:
    """Block until every process reaches this point (the role of the
    reference's blocking NetworkInit rendezvous) — a psum of 1 over a 1-D
    global mesh forces a cross-host collective."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = global_mesh()
    ones = jax.device_put(
        jnp.ones((jax.device_count(),), jnp.int32),
        NamedSharding(mesh, P("data")))

    @jax.jit
    def _sum(x):
        return x.sum()

    total = int(_sum(ones))
    assert total == jax.device_count(), (name, total)
